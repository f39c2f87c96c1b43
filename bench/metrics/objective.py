"""Objective of the result the user takes, recomputed by the benchmark's
plain reference: a partition's sum mu_e (lambda_e - 1), a schedule's BSP
cost."""


def read(ctx):
    return ctx.objective
