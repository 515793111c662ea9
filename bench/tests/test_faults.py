"""The harness on the CPU, at a size a test run holds: a sound run comes out
correct, and each fault a decode cell can have, planted under the timed
path, turns ``correct`` false: an altered answer, an output left as
initialised, half of a group left out, and a fused group's slots handed
each other's output.

    python3 -m pytest -q bench/tests

The harness's look for a TPU is skipped (``run.run_cell`` is called
directly); everything after it runs as on the chip, on the tiny
configuration and mixes beside this file (the Pallas kernel interpreted).
A one-chip decode cell has no exchange between chips, so that fault has no
test here.
"""

from __future__ import annotations

import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(HERE, "..", "..", "src"),
                os.path.join(HERE, "..", "..")]

from bench import load, run  # noqa: E402

SEED = 2 ** 31 + 17


def _json(name: str) -> dict:
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def _run(mix_name: str, fault=None) -> dict:
    config, mix = _json("tiny_config.json"), _json(f"{mix_name}.json")
    dep, setup = run.build(config, mix, SEED)
    if fault is not None:
        fault(dep)
    return run.measure("tiny", dep, setup, mix, [], SEED, 2.0, False,
                       time.perf_counter())


def altered_answer(dep) -> None:
    """One symbol of every answer altered where it is produced."""
    session = dep.svc.session
    execute = session.execute
    session.execute = lambda plan: execute(plan).at[0].add(1)


def unchanged_state(dep) -> None:
    """The decode returns its output buffer as initialised (-1), as if the
    walk never ran."""
    import jax.numpy as jnp
    session = dep.svc.session
    execute = session.execute
    session.execute = lambda plan: jnp.full_like(execute(plan), -1)


def half_batch(dep) -> None:
    """Every group dispatches only its first half of requests; the rest
    are never answered."""
    svc = dep.svc
    dispatch = svc.dispatch_group

    def first_half(requests, tickets):
        half = max(len(requests) // 2, 1)
        dispatch(requests[:half], tickets[:half])

    svc.dispatch_group = first_half


def _slot_fault(shuffle):
    """Plant ``shuffle`` on the per-request slice offsets of every fused
    group, so a ticket gets another slot's region of the output."""
    def plant(dep) -> None:
        svc = dep.svc
        group_plan = svc._group_plan

        def planted(reqs, record=True):
            plan, sym_off = group_plan(reqs, record)
            return plan, (None if sym_off is None else shuffle(sym_off))

        svc._group_plan = planted
    return plant


#: Every ticket sliced at offset 0: all slots share the first one's region.
slices_at_zero = _slot_fault(lambda off: [0] * len(off))
#: Each ticket sliced at the next slot's offset.
slots_rotated = _slot_fault(lambda off: off[1:] + off[:1])


@pytest.fixture
def short_wait(monkeypatch):
    monkeypatch.setattr(load, "ANSWER_WAIT_S", 3.0)


@pytest.mark.parametrize("mix_name", ["tiny_closed", "tiny_open", "tiny_own"])
def test_sound_run_is_correct(mix_name, short_wait):
    res = _run(mix_name)
    assert res["correct"], res["checks"]
    assert res["facts"]["compiles_in_window"] == 0
    assert res["checks"]["answers_checked"]["value"] >= 1


@pytest.mark.parametrize("fault", [altered_answer, unchanged_state,
                                   half_batch])
def test_fault_is_caught(fault, short_wait):
    res = _run("tiny_closed", fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [slices_at_zero, slots_rotated],
                         ids=["slices_at_zero", "slots_rotated"])
def test_slot_fault_is_caught(fault, short_wait):
    """Clients pinned to their own objects, as in ``rand50.c2048.bulk``:
    a fused group's slots hold different objects, so an answer sliced
    from another slot's region differs from its source."""
    res = _run("tiny_own", fault)
    assert not res["correct"], res["checks"]
    assert res["checks"]["wrong_symbols"]["value"] > 0


@pytest.mark.parametrize("mix_name", ["tiny_closed", "tiny_open"])
def test_control_is_caught(mix_name, short_wait):
    """The control (``bench/control.py``: symbols emitted through int8)
    fails the comparison that sound runs pass."""
    from bench import control
    res = _run(mix_name, control.int8_control)
    assert not res["correct"], res["checks"]
    assert res["checks"]["wrong_symbols"]["value"] > 0
