"""The arithmetic from stamps to end-to-end numbers.

An *update* is step k of entity n, due at ``due[k, n]``. A *delivery* is
a pair (update, client whose standing sphere covers the cell the update
puts the entity in). It is *reflected* when that client's socket first
reads a row of the entity with the update's sequence number (k + 1) or a
later one: channel data merges, so a later state stands for an earlier
one. Every time is taken from the update's due time, never from when it
was sent.
"""

from __future__ import annotations

import numpy as np

_SEQ_SPAN = 1 << 24  # more sequence numbers than any run sends


def first_reads(rec_n, rec_seq, rec_t, want_n, want_k) -> np.ndarray:
    """For each wanted update (entity n, index k), when one client first
    read a row of n with sequence >= k + 1; NaN where it never did.

    ``rec_*`` are that client's reads of rows whose sequence number was
    higher than any it had read of the entity before, in any order."""
    rec_n = np.asarray(rec_n, np.int64)
    key = rec_n * _SEQ_SPAN + np.asarray(rec_seq, np.int64)
    order = np.argsort(key, kind="stable")
    key, rec_n = key[order], rec_n[order]
    rec_t = np.asarray(rec_t, np.float64)[order]
    want_n = np.asarray(want_n, np.int64)
    want = want_n * _SEQ_SPAN + np.asarray(want_k, np.int64) + 1
    at = np.searchsorted(key, want, side="left")
    found = at < len(key)
    at = np.where(found, at, 0)
    found &= rec_n[at] == want_n if len(key) else False
    return np.where(found, rec_t[at] if len(key) else np.nan, np.nan)


def delivery_times(cells, due, covers, rec) -> tuple:
    """``(k, n, due f64[D], read f64[D])`` of one client's deliveries:
    every update whose cell the client covers, with its first read (NaN:
    never).

    ``cells`` i64[K, N] and ``due`` f64[K, N] are the schedule, ``covers``
    bool[C] the cells the client's sphere overlaps, ``rec`` the client's
    ``(n, seq, t)`` read columns."""
    k, n = np.nonzero(covers[cells])
    read = first_reads(rec[0], rec[1], rec[2], n, k)
    return k, n, due[k, n], read


def latency_ms(due, read, horizon: float) -> np.ndarray:
    """Milliseconds from due to read; a delivery never reflected stands
    at the horizon (the end of the drain), beyond any limit."""
    return (np.where(np.isnan(read), horizon, read) - due) * 1000.0


def percentile(values, q: float) -> float:
    """The q-th percentile by the nearest-rank rule over all values."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        raise ValueError("percentile of nothing")
    return float(v[min(len(v) - 1, max(0, int(np.ceil(q / 100.0 * len(v))) - 1))])


def crossings(start_cells, cells):
    """``(k, n, src, dst)`` columns of every update that carries its
    entity over a border, in due order of k."""
    prev = np.concatenate([np.asarray(start_cells)[None, :], cells[:-1]])
    k, n = np.nonzero(cells != prev)
    return k, n, prev[k, n], cells[k, n]


def handover_account(times: dict, owners: dict, seen: dict) -> dict:
    """Hold what the servers' sockets read against the reference.

    ``times``: {(n, src, dst): how often the reference has entity n cross
    from src to dst}. ``owners``: {(n, src, dst): the servers that own
    src and dst}; each must read that handover exactly ``times`` often.
    ``seen``: {(server, n, src, dst): times read}, over every server
    socket. A server with border interest also reads its neighbours'
    handovers: those are predicted when the reference has the crossing,
    and duplicated when read more often than it happens."""
    lost = sum(max(0, count - seen.get((server, *pair), 0))
               for pair, count in times.items() for server in owners[pair])
    duplicated = sum(max(0, got - times[key[1:]])
                     for key, got in seen.items() if key[1:] in times)
    unpredicted = sum(got for key, got in seen.items()
                      if key[1:] not in times)
    return {"handovers_lost": lost, "handovers_duplicated": duplicated,
            "handovers_unpredicted": unpredicted}
