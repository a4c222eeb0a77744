"""Test harness config: force an 8-device virtual CPU mesh BEFORE jax import.

This is how we test multi-chip sharding without TPU pods — the improvement
SURVEY.md §4 calls for over the reference (whose distributed tests were
excluded from CI as `notest_*`)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the session env may point at TPU
# hermetic: a test's result must not depend on what an earlier run left in
# <checkout>/.jax_cache (paddle_tpu/__init__.py places JAX's persistent
# compilation cache there); spawned workers inherit the switch
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
# zero-egress CI: datasets serve their synthetic stand-ins instead of
# stalling on download timeouts (test_datasets.py covers the real parse
# paths via local fixtures and clears this when exercising fallbacks)
os.environ.setdefault("PADDLE_TPU_SYNTHETIC", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax

assert len(jax.devices()) == 8, jax.devices()

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-process chaos/restart tests excluded from the "
        "tier-1 `-m 'not slow'` run")


@pytest.fixture
def fresh_programs():
    """Give a test its own main/startup programs and scope (the reference's
    tests do the same via new Program() + program_guard)."""
    from paddle_tpu import fluid

    main = fluid.Program()
    startup = fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        yield main, startup, scope


def rng(seed=0):
    return np.random.RandomState(seed)


def hlo_results_of_size(hlo: str, n_elems: int):
    """{opcode: count} of the instructions of an (optimized) HLO text
    whose result holds ``n_elems`` elements — how the paged-pool tests
    ask whether a compiled step still holds a second pool-sized buffer."""
    import re

    kinds = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                     r"([\w\-]+)\(", line)
        if m and int(np.prod([int(x) for x in m.group(1).split(",")])) \
                == n_elems:
            kinds[m.group(2)] = kinds.get(m.group(2), 0) + 1
    return kinds


def split_walks(call, group, monkeypatch):
    """``call()`` (the split paged-attention kernel, interpreted) with
    ``group`` table slots a grid step and with ONE: ``(got, one)``.
    ``group`` is a number forced in the rule's place, or ``"derived"``:
    the rule's own answer, which has to be more than one slot."""
    import importlib

    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    rule, seen = fa.split_slot_group, []

    def forced(*a, **k):
        seen.append(rule(*a, **k) if group == "derived" else group)
        return seen[-1]

    monkeypatch.setattr(fa, "split_slot_group", forced)
    got = np.asarray(call())
    assert seen and (group != "derived" or seen[0] > 1)
    monkeypatch.setattr(fa, "split_slot_group", lambda *a, **k: 1)
    return got, np.asarray(call())
