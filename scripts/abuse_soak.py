"""Abuse soak: three concurrent attacker classes against a live honest
fleet (doc/edge_hardening.md acceptance artifact).

Boots a real gateway (TCP listeners, the 1ms pump, the unauth reaper)
serving an honest client fleet whose every user-space frame is
delivery-accounted at the GLOBAL owner, then opens an attack window in
which three hostile classes run CONCURRENTLY, each from its own
loopback source range so the per-IP defenses stay attributable:

- **slow-reader** (127.0.1.x): subscribes to a flooded channel with a
  tiny SO_RCVBUF and stops reading. Must walk the full slow-consumer
  ladder — transport gate -> bounded envelope -> drop-to-full-resync ->
  quarantine -> structured disconnect — every step counted.
- **malformed-frame** (127.0.2.x): streams hostile byte sessions (bad
  magic, bad compression tags, garbage protobuf under valid framing).
  Each violation is counted at the stage that rejected it and is at
  worst connection-fatal.
- **connect-flood** (127.0.3.x): connects and never authenticates.
  Reaped at the auth deadline (-auth-deadline), IP-banned, and further
  connects from that source refused at accept.

Exit criteria (schema-gated by scripts/check_artifacts.py):

- honest census exact: every honest session still live and
  authenticated, the gateway's surviving connection set is exactly
  {master} + honest fleet (every attacker connection gone);
- honest delivery accounting intact: each client's drained sequence
  set at the owner equals exactly what it sent;
- every attacker quarantined / reaped / refused, with the edge plane's
  python ledgers equal to the prometheus counters (double-entry);
- RSS growth bounded across the attack.

Run the acceptance soak (~25s of timeline):
  python scripts/abuse_soak.py --out SOAK_ABUSE_r16.json
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

if os.environ.get("CHTPU_SOAK_TPU") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"  # gateway children inherit it

import argparse
import asyncio
import importlib.util
import json
import socket
import struct
import time
from dataclasses import dataclass
from random import Random


def _load_chaos_soak():
    """The chaos soak module provides the frame/auth/drain client
    machinery this soak re-drives against a hostile timeline."""
    spec = importlib.util.spec_from_file_location(
        "chaos_soak", os.path.join(REPO, "scripts", "chaos_soak.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("chaos_soak", mod)
    spec.loader.exec_module(mod)
    return mod


def _rss_mb() -> float:
    with open("/proc/self/statm") as f:
        pages = int(f.read().split()[1])
    return pages * os.sysconf("SC_PAGE_SIZE") / (1024.0 * 1024.0)


@dataclass
class AbuseSoakParams:
    attack_s: float = 14.0
    quiesce_s: float = 4.0
    honest: int = 8
    slow_readers: int = 3
    malformed: int = 3
    flood_ips: int = 3
    msg_rate: float = 30.0  # per honest client
    flood_rate: float = 150.0  # broadcasts/s to the slow readers
    flood_payload: int = 8192
    auth_deadline_ms: int = 1200
    rss_growth_mb_bound: float = 256.0
    seed: int = 0xAB05E
    out_path: str = ""


async def run_abuse_soak(p: AbuseSoakParams) -> dict:
    cs = _load_chaos_soak()

    from channeld_tpu.chaos.invariants import InvariantChecker, delta, scrape
    from channeld_tpu.core import channel as channel_mod
    from channeld_tpu.core import connection as connection_mod
    from channeld_tpu.core import data as data_mod
    from channeld_tpu.core import ddos as ddos_mod
    from channeld_tpu.core import edge
    from channeld_tpu.core import connection_recovery as recovery_mod
    from channeld_tpu.core.channel import get_global_channel
    from channeld_tpu.core.connection import all_connections, init_connections
    from channeld_tpu.core.ddos import (
        blacklist_snapshot,
        init_anti_ddos,
        unauth_reaper_loop,
    )
    from channeld_tpu.core.overload import reset_overload
    from channeld_tpu.core.server import flush_loop, start_listening
    from channeld_tpu.core.settings import (
        global_settings,
        reset_global_settings,
    )
    from channeld_tpu.core.types import (
        ChannelType,
        ConnectionState,
        ConnectionType,
        MessageType,
    )
    from channeld_tpu.federation import reset_federation
    from channeld_tpu.protocol import control_pb2, encode_packet, wire_pb2

    t_start = time.monotonic()
    rng = Random(p.seed)

    # -- fresh runtime (idempotent; the pytest smoke shares a process) --
    channel_mod.reset_channels()
    connection_mod.reset_connections()
    data_mod.reset_registries()
    ddos_mod.reset_ddos()
    recovery_mod.reset_recovery()
    reset_global_settings()
    reset_overload()
    reset_federation()

    global_settings.development = True
    # Side planes pinned OFF: this soak's envelope is the edge plane's
    # (each plane has its own soak; see their docs).
    global_settings.balancer_enabled = False
    # Adaptive partitioning stays pinned OFF: this soak's envelope
    # assumes the static boot grid (doc/partitioning.md);
    # scripts/density_soak.py is the partitioning plane's own soak.
    global_settings.partition_enabled = False
    global_settings.device_guard_enabled = False
    global_settings.slo_enabled = False
    global_settings.trace_enabled = False
    global_settings.federation_config = ""
    from channeld_tpu.core.tracing import recorder as _flight_recorder

    _flight_recorder.configure(enabled=False)

    # Edge knobs: shipping semantics, compressed time constants (the
    # ladder's graces are wall-clock; a soak-scale flood must walk it
    # in seconds, not minutes).
    global_settings.edge_send_queue_max_msgs = 512
    global_settings.edge_send_queue_max_bytes = 1 << 20
    global_settings.edge_slow_grace_s = 1.0
    global_settings.edge_quarantine_grace_s = 0.5
    global_settings.edge_transport_high_bytes = 128 * 1024
    global_settings.auth_deadline_ms = p.auth_deadline_ms

    init_connections(
        os.path.join(REPO, "config", "server_authoritative_fsm.json"),
        os.path.join(REPO, "config", "client_authoritative_fsm.json"),
    )
    init_channels_mod = channel_mod.init_channels
    init_channels_mod()
    init_anti_ddos()

    host = "127.0.0.1"
    server_srv = await start_listening(ConnectionType.SERVER, "tcp", f"{host}:0")
    server_port = server_srv.sockets[0].getsockname()[1]
    client_srv = await start_listening(ConnectionType.CLIENT, "tcp", f"{host}:0")
    client_port = client_srv.sockets[0].getsockname()[1]

    stop = asyncio.Event()
    send_stop = asyncio.Event()
    attack_over = asyncio.Event()
    tasks: list[asyncio.Task] = [
        asyncio.ensure_future(flush_loop()),
        asyncio.ensure_future(unauth_reaper_loop()),
    ]

    async def _connect_from(src_ip: str, rcvbuf: int = 0):
        """Connect to the CLIENT listener from a chosen loopback source
        (the per-IP defenses must stay attributable per attacker class);
        a small SO_RCVBUF makes 'stops reading' bite within soak-scale
        byte counts instead of megabytes of kernel buffering."""
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        if rcvbuf:
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
        sock.setblocking(False)
        sock.bind((src_ip, 0))
        try:
            await asyncio.get_running_loop().sock_connect(
                sock, (host, client_port))
        except OSError:
            sock.close()
            raise
        return await asyncio.open_connection(sock=sock)

    # -- master: GLOBAL owner + honest delivery drain + the flooder ----
    m_reader, m_writer = await cs._connect(host, server_port)
    await cs._auth_and_wait(m_reader, m_writer, "abuse-master")
    m_writer.write(cs._frame(
        MessageType.CREATE_CHANNEL,
        control_pb2.CreateChannelMessage(
            channelType=ChannelType.GLOBAL).SerializeToString(),
    ))
    await m_writer.drain()

    drained: dict[int, set] = {}

    def _on_master_pack(mp) -> None:
        if mp.msgType < 100:
            return
        sfm = wire_pb2.ServerForwardMessage()
        try:
            sfm.ParseFromString(mp.msgBody)
            cid, seq = struct.unpack("<II", sfm.payload[:8])
        except Exception:
            return
        drained.setdefault(cid, set()).add(seq)

    tasks.append(asyncio.ensure_future(
        cs._read_frames(m_reader, _on_master_pack, stop)))

    gch = get_global_channel()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and not gch.has_owner():
        await asyncio.sleep(0.05)
    if not gch.has_owner():
        raise RuntimeError("master never possessed GLOBAL")

    # -- honest fleet ---------------------------------------------------
    sent: dict[int, int] = {}
    honest_writers: list = []
    honest_drops = {"n": 0}

    async def _honest_client(idx: int) -> None:
        reader, writer = await cs._connect(host, client_port)
        await cs._auth_and_wait(reader, writer, f"honest-{idx}")
        honest_writers.append(writer)
        reader_task = asyncio.ensure_future(
            cs._read_frames(reader, lambda mp: None, stop))
        interval = 1.0 / p.msg_rate
        seq = 0
        try:
            while not stop.is_set():
                if reader_task.done():
                    honest_drops["n"] += 1
                    return
                if send_stop.is_set():
                    # Traffic cutoff hit: hold the socket open quietly —
                    # the census needs this session alive at the end.
                    await asyncio.sleep(0.2)
                    continue
                writer.write(cs._frame(100, struct.pack("<II", idx, seq)))
                await writer.drain()
                seq += 1
                sent[idx] = seq
                await asyncio.sleep(interval)
        except (ConnectionError, OSError):
            honest_drops["n"] += 1
        finally:
            reader_task.cancel()

    for idx in range(p.honest):
        tasks.append(asyncio.ensure_future(_honest_client(idx)))
    # Everyone authed and accounted before the attack window opens.
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and len(honest_writers) < p.honest:
        await asyncio.sleep(0.05)
    if len(honest_writers) < p.honest:
        raise RuntimeError("honest fleet failed to come up")

    # Timeline zero: the edge ledgers re-zero at the same instant the
    # metric baseline is scraped, so delta-vs-baseline == ledger holds
    # by construction (metrics are process-cumulative; ledgers are not).
    edge.reset_edge()
    baseline = scrape()
    rss_base = _rss_mb()
    rss_peak = {"mb": rss_base}
    envelope_breaches: list[str] = []

    async def _poller() -> None:
        while not stop.is_set():
            rss_peak["mb"] = max(rss_peak["mb"], _rss_mb())
            cap_m = global_settings.edge_send_queue_max_msgs
            cap_b = global_settings.edge_send_queue_max_bytes
            for conn in list(all_connections().values()):
                if len(conn.send_queue) > cap_m:
                    envelope_breaches.append(
                        f"conn {conn.id}: {len(conn.send_queue)} msgs")
                if conn.envelope.queue_bytes > cap_b:
                    envelope_breaches.append(
                        f"conn {conn.id}: {conn.envelope.queue_bytes} bytes")
            await asyncio.sleep(0.2)

    tasks.append(asyncio.ensure_future(_poller()))

    # -- attacker class 1: slow readers --------------------------------
    slow_stats = {"subscribed": 0, "sockets": []}

    async def _slow_reader(i: int) -> None:
        src = f"127.0.1.{i + 1}"
        try:
            reader, writer = await _connect_from(src, rcvbuf=8192)
        except OSError:
            return
        slow_stats["sockets"].append(writer)
        try:
            await cs._auth_and_wait(reader, writer, f"slow-{i}")
            writer.write(cs._frame(
                MessageType.SUB_TO_CHANNEL,
                control_pb2.SubscribedToChannelMessage(
                    subOptions=control_pb2.ChannelSubscriptionOptions(
                        dataAccess=1,  # READ: SHED-eligible
                    ),
                ).SerializeToString(),
            ))
            await writer.drain()
            # Drain the sub ack, then go silent: from here on the peer
            # reads NOTHING while the flood fills its socket.
            await asyncio.sleep(0.3)
            slow_stats["subscribed"] += 1
            await attack_over.wait()
        except (ConnectionError, OSError, TimeoutError):
            pass

    # -- attacker class 2: malformed frames -----------------------------
    mal_stats = {"sessions": 0, "gateway_closed": 0}

    def _hostile_bytes(r: Random) -> bytes:
        kind = r.randrange(3)
        if kind == 0:  # bad magic: framing-fatal at byte 0
            return b"XX" + bytes(r.randrange(256) for _ in range(16))
        if kind == 1:  # valid magic, undefined compression tag
            return b"CH" + struct.pack(">H", 32) + b"\x77" + bytes(32)
        # valid framing, garbage protobuf Packet body
        body = bytes(r.randrange(256) for _ in range(r.randrange(8, 64)))
        return b"CH" + struct.pack(">H", len(body)) + b"\x00" + body

    async def _malformed_attacker(i: int) -> None:
        src = f"127.0.2.{i + 1}"
        r = Random(p.seed ^ (0x600D + i))
        while not attack_over.is_set():
            try:
                reader, writer = await _connect_from(src)
            except OSError:
                await asyncio.sleep(0.3)
                continue
            mal_stats["sessions"] += 1
            try:
                for _ in range(r.randrange(1, 4)):
                    writer.write(_hostile_bytes(r))
                    await writer.drain()
                    await asyncio.sleep(0.02)
                data = await asyncio.wait_for(reader.read(4096), timeout=0.5)
                while data:
                    data = await asyncio.wait_for(
                        reader.read(4096), timeout=0.5)
                mal_stats["gateway_closed"] += 1  # EOF: connection-fatal
            except asyncio.TimeoutError:
                pass  # lingered (non-fatal stage); close our end
            except (ConnectionError, OSError):
                mal_stats["gateway_closed"] += 1
            finally:
                try:
                    writer.close()
                except Exception:
                    pass
            await asyncio.sleep(0.15)

    # -- attacker class 3: connect flood ---------------------------------
    flood_stats = {"sessions": 0, "reaped": 0, "refused": 0}

    async def _connect_flood(i: int) -> None:
        src = f"127.0.3.{i + 1}"
        while not attack_over.is_set():
            try:
                reader, writer = await _connect_from(src)
            except OSError:
                flood_stats["refused"] += 1
                await asyncio.sleep(0.3)
                continue
            flood_stats["sessions"] += 1
            t0 = time.monotonic()
            try:
                # Never authenticate; just hold the socket.
                data = await asyncio.wait_for(
                    reader.read(4096),
                    timeout=p.auth_deadline_ms / 1000.0 + 2.0)
                while data:
                    data = await asyncio.wait_for(reader.read(4096), 1.0)
            except (asyncio.TimeoutError, ConnectionError, OSError):
                pass
            held_s = time.monotonic() - t0
            # A socket cut near/after the deadline was reaped; one cut
            # immediately was refused at accept (the IP ban landed).
            if held_s >= p.auth_deadline_ms / 1000.0 * 0.5:
                flood_stats["reaped"] += 1
            else:
                flood_stats["refused"] += 1
            try:
                writer.close()
            except Exception:
                pass
            await asyncio.sleep(0.2)

    # -- the flood the slow readers must NOT keep up with ---------------
    async def _flooder() -> None:
        interval = 1.0 / p.flood_rate
        payload = bytes(p.flood_payload)
        body = wire_pb2.ServerForwardMessage(payload=payload).SerializeToString()
        frame = encode_packet(wire_pb2.Packet(messages=[wire_pb2.MessagePack(
            channelId=0, msgType=100, msgBody=body,
            broadcast=10,  # ALL | ALL_BUT_OWNER: subscribers minus master
        )]))
        while not attack_over.is_set():
            m_writer.write(frame)
            await m_writer.drain()
            await asyncio.sleep(interval)

    # -- attack window ---------------------------------------------------
    attack_tasks = [asyncio.ensure_future(_flooder())]
    for i in range(p.slow_readers):
        attack_tasks.append(asyncio.ensure_future(_slow_reader(i)))
    for i in range(p.malformed):
        attack_tasks.append(asyncio.ensure_future(_malformed_attacker(i)))
    for i in range(p.flood_ips):
        attack_tasks.append(asyncio.ensure_future(_connect_flood(i)))
    tasks.extend(attack_tasks)

    await asyncio.sleep(p.attack_s)
    attack_over.set()
    for w in slow_stats["sockets"]:
        try:
            w.close()
        except Exception:
            pass

    # -- quiesce: honest senders keep going while the attackers' wreckage
    # settles, then traffic stops and the last in-flight frames drain
    # into the master (the reader outlives the senders by design).
    await asyncio.sleep(p.quiesce_s)
    send_stop.set()
    await asyncio.sleep(0.3)  # let any mid-iteration write complete
    sent_final = dict(sent)
    await asyncio.sleep(1.0)  # let the last written frames reach the drain
    stop.set()
    await asyncio.sleep(0.1)

    # -- invariants -------------------------------------------------------
    inv = InvariantChecker()
    d = delta(scrape(), baseline)
    rss_final = _rss_mb()

    # 1. Honest census exact: the gateway's surviving connection set is
    # exactly {master} + the honest fleet, all authenticated.
    survivors = {
        c.pit: c for c in all_connections().values() if not c.is_closing()
    }
    expected_pits = {"abuse-master"} | {
        f"honest-{i}" for i in range(p.honest)
    }
    inv.expect_equal("honest_census_exact",
                     sorted(survivors), sorted(expected_pits))
    inv.expect_equal("no_honest_disconnects", honest_drops["n"], 0)
    inv.check(
        "all_survivors_authenticated",
        all(c.state == ConnectionState.AUTHENTICATED
            for c in survivors.values()),
        str({pit: c.state.name for pit, c in survivors.items()}),
    )

    # 2. Honest delivery accounting intact: every frame each honest
    # client sent before the cutoff was drained at the GLOBAL owner.
    missing = {
        idx: n - len(drained.get(idx, ()) & set(range(n)))
        for idx, n in sent_final.items()
        if len(drained.get(idx, set()) & set(range(n))) != n
    }
    inv.expect_equal("honest_delivery_exact", missing, {})
    total_sent = sum(sent_final.values())
    inv.expect_gt("honest_traffic_flowed", total_sent, 0)

    # 3. Every attacker dealt with, per class.
    led = edge.ledgers
    inv.expect_equal("slow_readers_engaged", slow_stats["subscribed"],
                     p.slow_readers)
    inv.expect_gt("slow_reader_ladder_dropped_to_resync",
                  led.egress_drop_counts.get("slow_consumer", 0), 0)
    inv.expect_equal("slow_readers_quarantined",
                     led.quarantine_counts.get("slow_consumer", 0),
                     p.slow_readers)
    inv.expect_equal("slow_readers_structurally_disconnected",
                     led.reap_counts.get("quarantine", 0), p.slow_readers)
    inv.expect_gt("malformed_sessions_ran", mal_stats["sessions"], 2)
    inv.expect_gt("malformed_counted_at_framing",
                  led.malformed_counts.get("framing", 0), 0)
    inv.expect_gt("malformed_sessions_connection_fatal",
                  mal_stats["gateway_closed"], 0)
    inv.expect_gt("flood_reaped_at_auth_deadline",
                  led.reap_counts.get("auth_timeout", 0), 0)
    banned_ips, _ = blacklist_snapshot()
    flood_srcs = {f"127.0.3.{i + 1}" for i in range(p.flood_ips)}
    inv.check("flood_sources_banned",
              flood_srcs <= set(banned_ips),
              f"banned={sorted(banned_ips)}")
    inv.expect_gt("flood_refused_after_ban", flood_stats["refused"], 0)
    inv.check("honest_sources_never_banned",
              "127.0.0.1" not in banned_ips,
              f"banned={sorted(banned_ips)}")

    # 4. Double-entry: every edge prometheus counter delta equals the
    # python ledger exactly (both started from zero at boot).
    def _family(name: str, label: str) -> dict:
        out: dict[str, int] = {}
        for (n, labels), v in d.items():
            if n == name and v:
                out[dict(labels)[label]] = int(v)
        return out

    inv.expect_equal("quarantine_ledger_matches_metric",
                     _family("conn_quarantine_total", "reason"),
                     led.quarantine_counts)
    inv.expect_equal("malformed_ledger_matches_metric",
                     _family("malformed_frames_total", "stage"),
                     led.malformed_counts)
    inv.expect_equal("egress_drop_ledger_matches_metric",
                     _family("egress_dropped_total", "reason"),
                     led.egress_drop_counts)
    inv.expect_equal("reap_ledger_matches_metric",
                     _family("conn_reaped_total", "reason"),
                     led.reap_counts)

    # 5. Resources bounded under attack.
    inv.expect_equal("no_envelope_breach", envelope_breaches[:8], [])
    rss_growth = rss_peak["mb"] - rss_base
    inv.expect_le("rss_growth_bounded_mb", round(rss_growth, 1),
                  p.rss_growth_mb_bound)

    report = {
        "kind": "abuse_soak",
        "duration_s": round(time.monotonic() - t_start, 2),
        "phases": {"attack_s": p.attack_s, "quiesce_s": p.quiesce_s},
        "seed": p.seed,
        "attackers": {
            "classes": ["slow_reader", "malformed_frame", "connect_flood"],
            "slow_reader": {"count": p.slow_readers, **{
                k: v for k, v in slow_stats.items() if k != "sockets"}},
            "malformed_frame": {"count": p.malformed, **mal_stats},
            "connect_flood": {"ips": p.flood_ips, **flood_stats},
        },
        "edge": {
            "quarantine": dict(led.quarantine_counts),
            "malformed": dict(led.malformed_counts),
            "egress_drops": dict(led.egress_drop_counts),
            "reaps": dict(led.reap_counts),
            "banned_ips": sorted(banned_ips),
        },
        "census": {
            "expected": sorted(expected_pits),
            "survivors": sorted(survivors),
            "honest_disconnects": honest_drops["n"],
        },
        "delivery": {
            "honest_clients": p.honest,
            "frames_sent": total_sent,
            "frames_drained": sum(len(v) for v in drained.values()),
            "missing": missing,
        },
        "rss": {
            "base_mb": round(rss_base, 1),
            "peak_mb": round(rss_peak["mb"], 1),
            "final_mb": round(rss_final, 1),
            "growth_mb": round(rss_growth, 1),
            "bound_mb": p.rss_growth_mb_bound,
        },
        "invariants": inv.summary(),
    }

    stop.set()
    for t in tasks:
        t.cancel()
    await asyncio.sleep(0)
    try:
        m_writer.close()
    except Exception:
        pass
    for w in honest_writers:
        try:
            w.close()
        except Exception:
            pass
    server_srv.close()
    client_srv.close()
    channel_mod.reset_channels()
    connection_mod.reset_connections()
    data_mod.reset_registries()
    ddos_mod.reset_ddos()
    recovery_mod.reset_recovery()
    reset_global_settings()
    reset_overload()

    if p.out_path:
        with open(p.out_path, "w") as f:
            json.dump(report, f, indent=2)
    return report


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--attack", type=float, default=14.0)
    ap.add_argument("--quiesce", type=float, default=4.0)
    ap.add_argument("--honest", type=int, default=8)
    ap.add_argument("--slow-readers", type=int, default=3)
    ap.add_argument("--malformed", type=int, default=3)
    ap.add_argument("--flood-ips", type=int, default=3)
    ap.add_argument("--out", type=str, default="")
    args = ap.parse_args()
    p = AbuseSoakParams(
        attack_s=args.attack, quiesce_s=args.quiesce, honest=args.honest,
        slow_readers=args.slow_readers, malformed=args.malformed,
        flood_ips=args.flood_ips, out_path=args.out,
    )
    report = asyncio.run(run_abuse_soak(p))
    print(json.dumps(report, indent=2))
    if not report["invariants"]["ok"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
