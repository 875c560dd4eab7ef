"""Tier-1 collects the family cases of the benchmark's own
``perfbench/tests/test_families.py`` (which ``pytest tests/`` does not
reach), for every family ``BENCHMARK.json`` uses.

Three of its cases are taken as they are. The fourth, that only a family's
own file names a model or reads its fields, is written here for SEVERAL
families: the original exempts ``families/qwen3.py`` by name, and a
``model_config`` PR may not edit a file the benchmark already has, so run by
hand it now flags ``families/deepseek_v3.py`` (PERF.md section 7)."""

import importlib.util
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")

_spec = importlib.util.spec_from_file_location(
    "perfbench_tests_test_families",
    os.path.join(BENCH, "tests", "test_families.py"))
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

test_every_configuration_states_a_family_that_gives_the_whole_interface = \
    _cases.test_every_configuration_states_a_family_that_gives_the_whole_interface
test_there_is_no_default_family = _cases.test_there_is_no_default_family
test_a_module_short_of_the_interface_is_refused = \
    _cases.test_a_module_short_of_the_interface_is_refused

# What only a family's own file may say: its model's name, the model's class
# in the program, a field of its block.
OF_A_FAMILY = {
    "qwen3": _cases.OF_A_FAMILY,
    "deepseek_v3": re.compile(
        r"deepseek|joyai|DeepseekV3Config|kv_lora|q_lora|qk_nope|qk_rope|"
        r"n_routed|routed_scaling|first_k_dense|kv_rank|router_width",
        re.IGNORECASE),
}


def sources():
    for folder, _, files in os.walk(BENCH):
        rel = os.path.relpath(folder, BENCH)
        if rel.split(os.sep)[0] in ("tests", "__pycache__"):
            continue
        for name in files:
            if name.endswith(".py"):
                yield os.path.relpath(os.path.join(folder, name), BENCH)


def test_there_is_a_pattern_for_every_family():
    from perfbench import families

    assert sorted(OF_A_FAMILY) == families.known()


@pytest.mark.parametrize("family", sorted(OF_A_FAMILY))
def test_only_a_familys_own_file_names_its_model_or_reads_its_fields(family):
    """``core``, ``system``, ``check``, the reference's driver, every reader
    and every OTHER family's file say nothing of this family's model: a
    family of another architecture is files only. (A reader reaches a
    family's counts as ``rec.family.<count>``, by names that are not the
    model's.)"""
    own = os.path.join("families", family + ".py")
    seen = 0
    for rel in sources():
        with open(os.path.join(BENCH, rel)) as f:
            hit = OF_A_FAMILY[family].search(f.read())
        if rel == own:
            assert hit is not None
        elif rel.startswith("families" + os.sep) and \
                rel != os.path.join("families", "__init__.py"):
            # another family's file: it may read shared public keys
            # (head_dim, rope_theta) but not name this family's model
            continue
        else:
            assert hit is None, f"perfbench/{rel}: {hit.group(0)!r}"
        seen += 1
    assert seen > 30
