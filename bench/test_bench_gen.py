"""Instance generators found by name: the built-in ones build the same bytes
as before, a generator added as a file under ``bench/generators`` is found
without an edit of any other file, an unknown name says where it was
looked for, and no generator file imports the program."""
from __future__ import annotations

import hashlib
import re

import numpy as np
import pytest

from bench import gen, harness

CHAIN = '''
import numpy as np

from bench import gen


def build(n):
    return {"n": n, "src": np.arange(n - 1), "dst": np.arange(1, n),
            "omega": np.arange(1.0, n + 1.0), "mu": np.ones(n)}


relabel = gen.relabel_dag
'''


def _digest(inst: dict) -> str:
    h = hashlib.sha256()
    for key in sorted(inst):
        a = np.asarray(inst[key])
        h.update(f"{key}:{a.dtype.str}:{a.shape};".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


# sha256 of each configuration's instance, as built before generators could
# be added as files
PINNED = {
    ("rownet_p8", 0): "454ff6599023677e2f60b49887474420c99cb7347eb974d37275da77e9e6fff7",
    ("rownet_p8", 1): "523bf08e3cfbfdd2f6b3352d81d0f06efa981caa40183b53a35a0ae86629d542",
    ("cholesky_bsp8", 0): "5497a737b3abcc48bea64d9eadcaa4a4abdeca1e563ec83b28d4a5d694c69898",
    ("cholesky_bsp8", 1): "308c3d1bb47836fdd5a2b7435f5edc9569a82ca7effc264a37e9584cc0f91035",
}


@pytest.mark.parametrize("config,seed", sorted(PINNED))
def test_builtin_instances_unchanged(config, seed):
    spec = harness.load_json(harness.BENCH / "configs" / f"{config}.json")
    assert _digest(gen.instance(spec["instance"], seed)) == PINNED[config,
                                                                   seed]


def test_generator_file_found_by_name(tmp_path, monkeypatch):
    (tmp_path / "chain20.py").write_text(CHAIN)
    monkeypatch.setattr(gen, "GENERATOR_DIR", tmp_path)
    inst = gen.instance({"generator": "chain20", "n": 20}, seed=7)
    chain = {"n": 20, "src": np.arange(19), "dst": np.arange(1, 20),
             "omega": np.arange(1.0, 21.0), "mu": np.ones(20)}
    want = gen.relabel_dag(chain, 7)
    assert set(inst) == set(want)
    for key in want:
        np.testing.assert_array_equal(inst[key], want[key])
    # a relabelled chain: every node but one has one parent
    assert inst["n"] == 20 and len(inst["src"]) == 19
    assert sorted(np.bincount(inst["dst"], minlength=20)) == [0] + [1] * 19


def test_unknown_generator_names_both_places(tmp_path, monkeypatch):
    monkeypatch.setattr(gen, "GENERATOR_DIR", tmp_path)
    with pytest.raises(KeyError) as info:
        gen.instance({"generator": "no_such_dag", "n": 3}, seed=0)
    msg = str(info.value)
    assert "GENERATORS" in msg and str(tmp_path / "no_such_dag.py") in msg


def test_generator_files_import_nothing_of_the_program():
    files = sorted(gen.GENERATOR_DIR.glob("*.py"))
    assert files                              # __init__.py at least
    for path in files + [harness.BENCH / "gen.py"]:
        code = "\n".join(line for line in path.read_text().splitlines()
                         if not line.lstrip().startswith("#"))
        assert not re.search(r"^\s*(from|import)\s+repro\b", code,
                             re.MULTILINE), path
