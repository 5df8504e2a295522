"""Shared test fixtures: fake connections and transports.

Mirrors the reference's two injection seams (ref: SURVEY §4): a
message-capturing sender (testQueuedMessageSender) and a pure stub
connection (testConnection) implementing the connection-in-channel
surface.
"""

from __future__ import annotations

from typing import Optional

from channeld_tpu.core.types import ConnectionState, ConnectionType
from channeld_tpu.utils.anyutil import unpack_any


class FakeTransport:
    """In-memory byte sink."""

    def __init__(self):
        self.written: list[bytes] = []
        self.closed = False

    def write(self, data: bytes) -> None:
        self.written.append(data)

    def close(self) -> None:
        self.closed = True

    def remote_addr(self) -> Optional[tuple]:
        return ("127.0.0.1", 9999)


class StubConnection:
    """Pure stub implementing the connection surface channels touch
    (ref: spatial_test.go testConnection)."""

    def __init__(self, conn_id: int, conn_type=ConnectionType.CLIENT):
        self.id = conn_id
        self.connection_type = conn_type
        self.state = ConnectionState.AUTHENTICATED
        self.pit = f"pit{conn_id}"
        self.recover_handle = None
        self.spatial_subscriptions: dict[int, object] = {}
        self.fsm_disallowed_counter = 0
        self.sent: list = []  # MessageContext
        from channeld_tpu.utils.logger import get_logger

        self.logger = get_logger(f"stub.{conn_id}")

    def is_closing(self) -> bool:
        return self.state >= ConnectionState.CLOSING

    def close(self, unexpected: bool = False) -> None:
        self.state = ConnectionState.CLOSING

    def send(self, ctx) -> None:
        self.sent.append(ctx)

    def should_recover(self) -> bool:
        return self.recover_handle is not None

    def on_authenticated(self, pit: str) -> None:
        self.pit = pit

    def has_interest_in(self, ch_id: int) -> bool:
        return ch_id in self.spatial_subscriptions

    def has_authority_over(self, ch) -> bool:
        from channeld_tpu.core.channel import get_global_channel

        gch = get_global_channel()
        if gch is not None and gch.get_owner() is self:
            return True
        return ch.get_owner() is self

    def remote_addr(self):
        return ("127.0.0.1", 10000 + self.id)

    def remote_ip(self):
        return "127.0.0.1"

    def disconnect(self):
        pass

    # -- test helpers --
    def data_updates(self) -> list:
        """Unpacked payloads of CHANNEL_DATA_UPDATE messages sent to us."""
        out = []
        for ctx in self.sent:
            if ctx.msg_type == 8:
                out.append(unpack_any(ctx.msg.data))
        return out

    def latest_data_update(self):
        updates = self.data_updates()
        return updates[-1] if updates else None


def fresh_runtime():
    """Reset all process-wide registries and create the GLOBAL channel."""
    from channeld_tpu.core import channel as channel_mod
    from channeld_tpu.core import connection as connection_mod
    from channeld_tpu.core import data as data_mod
    from channeld_tpu.core import ddos as ddos_mod
    from channeld_tpu.core import connection_recovery as recovery_mod
    from channeld_tpu.core.message import init_message_map
    from channeld_tpu.core.overload import reset_overload
    from channeld_tpu.spatial.controller import reset_spatial_controller

    channel_mod.reset_channels()
    connection_mod.reset_connections()
    data_mod.reset_registries()
    ddos_mod.reset_ddos()
    recovery_mod.reset_recovery()
    reset_spatial_controller()
    reset_overload()
    init_message_map()
    channel_mod.init_channels()
    return channel_mod.get_global_channel()


def stage_count(stage: str) -> float:
    """Observations of one ``tick_stage_ms`` stage so far."""
    from channeld_tpu.core import metrics

    child = metrics.tick_stage_ms.labels(stage=stage)
    return sum(b.get() for b in child._buckets)


class FetchCounter:
    """Counts device->host fetches while it is entered: every readback
    in the codebase is an ``np.asarray`` on a jax array (the tpulint
    hot-readback rule holds the idiom), so a counting ``np.asarray`` in
    numpy's own namespace sees them all, on every thread."""

    def __init__(self):
        self.count = 0

    def __enter__(self):
        import jax
        import numpy as np

        self._orig = orig = np.asarray

        def counting(a, *args, **kwargs):
            if isinstance(a, jax.Array):
                self.count += 1
            return orig(a, *args, **kwargs)

        np.asarray = counting
        return self

    def __exit__(self, *exc):
        import numpy as np

        np.asarray = self._orig
