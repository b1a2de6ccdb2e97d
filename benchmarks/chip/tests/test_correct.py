"""``correct`` at a size a CPU test holds: a sound run passes the cell's
committed limits; the control and every fault the cell can have fail them.

Serving's control is the reference with 4-bit weights and KV in the
program's place; training's is the program's own 4-bit gradient and moment
path, held against the reference at the stated 8 bits. The faults (faults.py) are planted under the timed path; the rest of
the run is the harness's, only the look for a chip is skipped.
"""
import pytest

import faults
from conftest import drive


def failed(out: dict) -> list:
    return [c["name"] for c in out["checks"] if c["value"] > c["limit"]]


def test_serve_sound_run_is_correct(serve_spec):
    out = drive(serve_spec, seed=2 ** 31 + 17)
    assert out["attempted"] > 0 and not failed(out)


def test_serve_control_fails(serve_spec):
    out = drive(serve_spec, seed=2 ** 31 + 17, control=True)
    limit = serve_spec["workload"]["check"]["served_token_gap"]
    assert out["readings"]["control_gap"] > limit


@pytest.mark.parametrize("fault", sorted(faults.SERVE))
def test_serve_fault_is_not_correct(serve_spec, fault):
    out = drive(serve_spec, seed=2 ** 31 + 17, fault=faults.SERVE[fault])
    assert failed(out) == ["served_token_gap"]


def test_train_sound_run_is_correct(train_spec):
    out = drive(train_spec, seed=2 ** 31 + 23, seconds=0.5)
    assert out["attempted"] > 0 and not failed(out)


def test_train_control_fails(train_spec):
    out = drive(train_spec, seed=2 ** 31 + 23, seconds=0.5, control=True)
    assert failed(out)


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_train_fault_is_not_correct(train_spec, fault):
    out = drive(train_spec, seed=2 ** 31 + 23, seconds=0.5,
                fault=faults.TRAIN[fault])
    assert failed(out)
