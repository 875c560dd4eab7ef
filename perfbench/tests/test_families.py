"""The seam between the harness and a model family: what is cheap to hold
(no compile). The whole run of a family that only a test adds is in
``test_harness.py``; the family's counts are pinned in ``test_arithmetic.py``
and its forward pass against the program's in ``test_reference.py``."""

import json
import os
import re

import pytest

from perfbench import core, families

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SIZES_EVERY_FAMILY_GIVES = ("vocab_size", "max_length", "dtype", "n_layers",
                            "d_model")
# What only a family's own file may say: a model's name, its class in the
# program, or a field of a dense grouped-query decoder.
OF_A_FAMILY = re.compile(
    r"qwen|ModelConfig|models\.config|n_kv_heads|n_heads|head_dim|d_ff|"
    r"rope_theta|rms_eps|tie_embeddings|qk_norm", re.IGNORECASE)


def harness_sources():
    for folder, _, files in os.walk(BENCH):
        rel = os.path.relpath(folder, BENCH)
        if rel.split(os.sep)[0] in ("tests", "__pycache__"):
            continue
        for name in files:
            path = os.path.join(folder, name)
            if name.endswith(".py") and \
                    os.path.relpath(path, BENCH) != "families/qwen3.py":
                yield path


def test_every_configuration_states_a_family_that_gives_the_whole_interface():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["configs"]
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        family = families.load_family(cfg)
        assert family.__name__ == f"perfbench.families.{cfg['family']}"
        assert all(callable(getattr(family, k)) for k in families.INTERFACE)
        sizes = family.sizes(cfg)
        assert all(hasattr(sizes, k) for k in SIZES_EVERY_FAMILY_GIVES)
        hash(sizes)          # a static argument of the family's jitted calls
        assert family.decode_step_min_bytes(sizes, [1000]) > \
            family.decode_step_min_bytes(sizes, [0]) > 0


def test_there_is_no_default_family():
    for cfg in ({}, {"family": None}, {"family": "no_such"},
                {"family": "../qwen3"}, {"family": "__init__"}):
        with pytest.raises(core.BenchFailure, match=r"there are: \[.*'qwen3'"):
            families.load_family(cfg)


def test_a_module_short_of_the_interface_is_refused(tmp_path):
    (tmp_path / "halfway.py").write_text("def sizes(cfg):\n    return cfg\n")
    families.__path__.append(str(tmp_path))
    try:
        with pytest.raises(core.BenchFailure, match="lacks.*layer_forward"):
            families.load_family({"family": "halfway"})
    finally:
        families.__path__.pop()


def test_only_its_own_file_names_a_model_or_reads_its_fields():
    """``core``, ``system``, ``check``, ``sweep``, ``limits``, the reference's
    driver and every reader import no model and read no field of a dense
    grouped-query decoder: a family of another architecture is then files
    only."""
    seen = 0
    for path in harness_sources():
        with open(path) as f:
            text = f.read()
        hit = OF_A_FAMILY.search(text)
        assert hit is None, f"{os.path.relpath(path, ROOT)}: {hit.group(0)!r}"
        seen += 1
    assert seen > 30
    with open(os.path.join(BENCH, "families", "qwen3.py")) as f:
        assert OF_A_FAMILY.search(f.read())
