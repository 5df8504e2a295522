"""The send writer's queue delay reader on a hand-made ``ctx``: a value
where the writer's counters are in the ``/metrics`` delta, ``None``,
never 0, where they are not (the parent) or no batch went out."""

import os

import pytest

from benchmark.harness import driver

BASE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(metrics):
    reader = driver.load_file(
        os.path.join(BASE, "layer_metrics", "send_writer_wait_us.py"),
        "layer_metric_send_writer_wait_us")
    return reader.read({"metrics": metrics, "wall_s": 20.0, "trace": None,
                        "base": BASE})


def sample(name, value):
    return {(name, ()): value}


def test_the_wait_a_batch_in_microseconds():
    # 104,000 hand-offs over a window that waited 6.24 s in all.
    metrics = {**sample("send_writer_wait_seconds_total", 6.24),
               **sample("send_writer_batches_total", 104_000),
               **sample("send_pump_write_seconds_total", 4.4)}
    assert read(metrics) == pytest.approx(60.0)


@pytest.mark.parametrize("metrics", [
    {},
    {("send_pump_write_seconds_total", ()): 4.4},
    {("send_writer_wait_seconds_total", ()): 0.0,
     ("send_writer_batches_total", ()): 0.0},
], ids=["parent", "no-writer-counters", "no-batch"])
def test_none_without_the_counters(metrics):
    assert read(metrics) is None
