"""The spatial flow's account, held to a run recorded before the harness
was split into a shared driver and flows: the same record gives the same
result, number for number and check for check.

The record is one CPU rehearsal of ``tiny-tanks.amble`` (``rehearse.py``,
seed 7, traced), taken at the call of the pre-split ``driver.account``:
the ``run`` it received, the reduced trace it read and the result it
returned, in ``data/tiny-tanks.amble.record.json``. Arrays, tuples,
sets, numpy scalars, the reference's ``Grid`` and dicts with keys that
are not strings are tagged (``encode`` / ``decode``), so the record reads
back to the very values the driver held.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

from benchmark.harness.reference import Grid

HERE = os.path.dirname(os.path.abspath(__file__))
RECORD = os.path.join(HERE, "data", "tiny-tanks.amble.record.json")


def encode(value):
    """``value`` as JSON-able data, every type tagged that JSON would lose."""
    if isinstance(value, np.ndarray):
        return {"__array__": value.dtype.str, "shape": list(value.shape),
                "data": value.ravel().tolist()}
    if isinstance(value, np.generic):
        return {"__scalar__": value.dtype.str, "value": value.item()}
    if isinstance(value, Grid):
        return {"__grid__": encode(dataclasses.asdict(value))}
    if isinstance(value, tuple):
        return {"__tuple__": [encode(v) for v in value]}
    if isinstance(value, (set, frozenset)):
        return {"__frozenset__" if isinstance(value, frozenset) else "__set__":
                sorted(encode(v) for v in value)}
    if isinstance(value, list):
        return [encode(v) for v in value]
    if isinstance(value, dict):
        if all(isinstance(k, str) for k in value):
            return {k: encode(v) for k, v in value.items()}
        return {"__items__": [[encode(k), encode(v)] for k, v in value.items()]}
    return value


def decode(value):
    if isinstance(value, list):
        return [decode(v) for v in value]
    if not isinstance(value, dict):
        return value
    if "__array__" in value:
        return np.array(value["data"], np.dtype(value["__array__"])).reshape(
            value["shape"])
    if "__scalar__" in value:
        return np.dtype(value["__scalar__"]).type(value["value"])
    if "__grid__" in value:
        return Grid(**decode(value["__grid__"]))
    if "__tuple__" in value:
        return tuple(decode(v) for v in value["__tuple__"])
    if "__set__" in value:
        return {decode(v) for v in value["__set__"]}
    if "__frozenset__" in value:
        return frozenset(decode(v) for v in value["__frozenset__"])
    if "__items__" in value:
        return {decode(k): decode(v) for k, v in value["__items__"]}
    return {k: decode(v) for k, v in value.items()}


def test_the_spatial_flow_accounts_for_the_recorded_run_as_before(tmp_path,
                                                                   monkeypatch):
    from benchmark.harness import driver
    from benchmark.tests import rehearse

    with open(RECORD) as f:
        record = decode(json.load(f))
    run, before = record["run"], record["result"]
    # The pre-split run held each peer kind's answers under a name of its
    # own; the split driver holds them by kind.
    run["answers"] = {"sender": run.pop("sent"),
                      "receiver": run.pop("received")}
    monkeypatch.setattr(driver, "reduce_trace",
                        lambda path: decode(json.load(open(RECORD)))["reduced"])
    cell = driver.load_cell(rehearse.make_root(str(tmp_path)),
                            "tiny-tanks.amble")
    after = driver.assemble(cell, driver.load_flow(cell), run)
    assert list(after) == list(before)  # checks last, as before
    # Only the order of the checks moved: the two that can read non-zero
    # on a sound program come first.
    assert list(after["checks"])[:2] == ["device_faults", "updates_unsent"]
    assert sorted(after["checks"]) == sorted(before["checks"])
    assert after == before
