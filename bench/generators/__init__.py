"""Instance generators of configurations added as files.

A configuration whose ``instance.generator`` is not one of
``bench/gen.py``'s built-in ``GENERATORS`` names a file here,
``<generator>.py``, which ``bench.gen.instance`` loads by that name.  It
defines:

* ``build(**params) -> dict``: the instance from the configuration's other
  ``instance`` keys, as flat numpy arrays: ``n``, ``src``, ``dst``,
  ``omega``, ``mu`` for a DAG, or ``n``, ``xpins``, ``pins``, ``omega`` for
  a hypergraph, deterministic;
* ``relabel(inst, seed) -> dict``: the instance relabelled by a seed, which
  may simply be ``bench.gen.relabel_dag`` or
  ``bench.gen.relabel_hypergraph``.

A generator file, like ``bench/gen.py``, imports nothing of the program
(``repro``): the instance and the plain reference stay independent of the
code under test.
"""
