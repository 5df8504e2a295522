"""The latency arithmetic on synthetic stamp logs."""

import numpy as np

from benchmark.harness import stats


def test_time_is_taken_from_the_due_time_not_the_send_time():
    # One entity, two updates due at 10.0 and 10.5, both in a covered
    # cell. The first was read at 10.2 however late it was sent.
    cells = np.array([[3], [3]])
    due = np.array([[10.0], [10.5]])
    covers = np.zeros(4, bool)
    covers[3] = True
    rec = (np.array([0, 0]), np.array([1, 2]), np.array([10.2, 10.9]))
    k, n, d, read = stats.delivery_times(cells, due, covers, rec)
    assert list(stats.latency_ms(d, read, horizon=20.0).round(6)) == [200.0, 400.0]


def test_a_later_state_stands_for_a_superseded_update():
    # Updates 0..3; the client reads sequence 1, then 4: updates 1 and 2
    # were merged away and are reflected when 4 arrives.
    got = stats.first_reads([0, 0], [4, 1], [3.0, 1.0], [0, 0, 0, 0], [0, 1, 2, 3])
    assert list(got) == [1.0, 3.0, 3.0, 3.0]


def test_a_delivery_never_reflected_stands_at_the_horizon():
    got = stats.first_reads([0], [1], [1.0], [0, 1], [1, 0])
    assert np.isnan(got).all()  # no row of seq >= 2, no row of entity 1
    lat = stats.latency_ms(np.array([5.0, 5.0]), got, horizon=12.0)
    assert list(lat) == [7000.0, 7000.0]
    assert stats.percentile(np.append(lat, [10.0] * 18), 95) == 7000.0


def test_only_covered_cells_make_deliveries():
    cells = np.array([[0, 1], [1, 1]])
    due = np.array([[0.0, 0.0], [1.0, 1.0]])
    covers = np.array([False, True])
    k, n, d, read = stats.delivery_times(cells, due, covers,
                                         (np.zeros(0), np.zeros(0), np.zeros(0)))
    assert sorted(zip(k.tolist(), n.tolist())) == [(0, 1), (1, 0), (1, 1)]
    assert np.isnan(read).all()


def test_percentile_is_nearest_rank_over_all_values():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50
    assert stats.percentile(v, 95) == 95
    assert stats.percentile([7.0], 95) == 7.0


def test_crossings_and_the_handover_account():
    start = np.array([0, 5])
    cells = np.array([[0, 5], [1, 5], [0, 6], [1, 6]])
    k, n, src, dst = stats.crossings(start, cells)
    assert list(zip(k, n, src, dst)) == [(1, 0, 0, 1), (2, 0, 1, 0),
                                         (2, 1, 5, 6), (3, 0, 0, 1)]
    times = {(0, 0, 1): 2, (0, 1, 0): 1, (1, 5, 6): 1}
    owners = {(0, 0, 1): {0}, (0, 1, 0): {0}, (1, 5, 6): {1, 2}}
    seen = {(0, 0, 0, 1): 2, (0, 0, 1, 0): 1, (1, 1, 5, 6): 1, (2, 1, 5, 6): 1,
            (3, 1, 5, 6): 1}  # server 3 borders the cells and reads it too
    assert stats.handover_account(times, owners, seen) == {
        "handovers_lost": 0, "handovers_duplicated": 0,
        "handovers_unpredicted": 0}
    seen[(2, 1, 5, 6)] = 0  # the destination's owner never read it
    seen[(3, 1, 5, 6)] = 2  # a neighbour read it twice
    seen[(0, 1, 6, 5)] = 1  # and nobody predicted this one
    assert stats.handover_account(times, owners, seen) == {
        "handovers_lost": 1, "handovers_duplicated": 1,
        "handovers_unpredicted": 1}


def test_cell_row_staleness_reads_every_row_of_the_tables_or_nothing():
    import os

    from benchmark.harness.driver import load_file

    base = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "layer_metrics")
    rows = np.array([100.0, 300.0, 200.0, 5000.0])
    p50 = load_file(os.path.join(base, "cell_row_stale_p50_ms.py"), "p50")
    p95 = load_file(os.path.join(base, "cell_row_stale_p95_ms.py"), "p95")
    assert p50.read({"cell_rows_ms": rows}) == 200.0
    assert p95.read({"cell_rows_ms": rows}) == 5000.0
    assert p50.read({"cell_rows_ms": np.array([])}) is None
