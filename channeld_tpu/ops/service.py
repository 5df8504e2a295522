"""gRPC sidecar exposing the TPU spatial decision plane.

Lets an external gateway (e.g. the original Go channeld behind its
SpatialController seam) offload the per-tick AOI/handover/fan-out pass:
it ships position deltas + query/subscription changes in a StepRequest
and receives compacted decisions. Service wiring is hand-rolled generic
handlers because the image carries only the grpc runtime (no codegen
plugin); the message schema is service.proto.

Serving properties:
- Interest results are DELTA: AOI masks depend only on query geometry,
  so only connections whose query changed this step are recomputed and
  returned (request fullInterest for a complete sync). Step cost is
  therefore independent of the standing query population. Dirty
  tracking is per caller (per stream / per unary peer), so concurrent
  gateway clients each see every change exactly once; a caller's first
  step is automatically a full sync.
- Steps serialize per engine (not on a global lock): a long device step
  never blocks Configure, and an engine swap never waits on traffic to
  a doomed engine.
- Optional shared-secret auth: set ``auth_token`` (or the
  CHTPU_SIDECAR_TOKEN env var) and every call must carry it as
  ``x-chtpu-auth`` metadata.
- StepStream: a bidirectional pipeline (one response per request)
  avoiding per-call RPC setup at the 30Hz gateway cadence.
"""

from __future__ import annotations

import os
import threading
import time
import uuid
from concurrent import futures
from typing import Optional

import numpy as np

from ..utils.logger import get_logger
from .spatial_ops import AOI_SPOTS
from .service_pb2 import (
    ConfigRequest,
    Empty,
    StepRequest,
    StepResponse,
)

logger = get_logger("ops.service")

SERVICE_NAME = "chtpu.ops.SpatialDecision"
AUTH_METADATA_KEY = "x-chtpu-auth"
# Distinguishes unary callers for delta-interest tracking. context.peer()
# alone is NOT enough: grpc-python shares subchannels between channels
# with the same target+args, so two client objects in one process can
# present the same peer address.
CALLER_METADATA_KEY = "x-chtpu-caller"


class _StepValidationError(ValueError):
    """A malformed StepRequest; unary aborts, streaming reports in-band."""


_DIRTY_CALLER_TTL = 300.0  # forget unary peers silent this long
_MAX_DIRTY_CALLERS = 64  # hard cap: caller ids are client-controlled


class _EngineState:
    """One engine plus ALL its serving state, swapped atomically on
    Configure: a step racing a swap holds the doomed state's lock and
    touches only that state — never the new engine's dirty set/sub map.

    Dirty-interest tracking is PER CALLER (one set per stream, one per
    unary peer): a query mutation marks the conn dirty in every caller's
    set, and each caller's step drains only its own — so a unary Step
    racing a StepStream (or two gateway clients) can't consume each
    other's pending delta-interest notifications. A caller seen for the
    first time starts with every standing query dirty, so its first step
    is a full sync without needing fullInterest."""

    def __init__(self, engine):
        self.engine = engine
        self.lock = threading.Lock()
        self.sub_map: dict[int, int] = {}
        self._dirty_sets: dict[object, set[int]] = {}
        self._dirty_seen: dict[object, float] = {}
        self._pinned: set[object] = set()  # stream callers: no TTL/evict

    def dirty_for(self, caller: object, pinned: bool = False) -> set[int]:
        """The caller's own dirty set (created on first use). The
        registry is bounded two ways — caller ids are client-controlled
        metadata, so it must not grow with hostile or buggy traffic:
        unary peers unseen within the TTL are pruned, and at the hard
        cap the longest-unseen unary peer is evicted (it full-resyncs on
        return). ``pinned`` callers (open streams) are exempt from both;
        stream teardown drops them explicitly."""
        now = time.monotonic()
        dirty = self._dirty_sets.get(caller)
        if dirty is None:
            # Only unpinned callers count toward (and make room in) the
            # cap: a new pinned stream must not evict a unary caller's
            # pending deltas to claim a slot it is itself exempt from.
            if not pinned:
                unpinned = [k for k in self._dirty_seen
                            if k not in self._pinned]
                if len(unpinned) >= _MAX_DIRTY_CALLERS:
                    self.drop_caller(min(unpinned, key=self._dirty_seen.get))
            dirty = set(self.engine._q_of_conn.keys())
            self._dirty_sets[caller] = dirty
            if pinned:
                self._pinned.add(caller)
        self._dirty_seen[caller] = now
        for stale in [k for k, t in self._dirty_seen.items()
                      if now - t > _DIRTY_CALLER_TTL
                      and k not in self._pinned]:
            self.drop_caller(stale)
        return dirty

    def drop_caller(self, caller: object) -> None:
        self._dirty_sets.pop(caller, None)
        self._dirty_seen.pop(caller, None)
        self._pinned.discard(caller)

    def mark_dirty(self, conn_id: int) -> None:
        for dirty in self._dirty_sets.values():
            dirty.add(conn_id)

    def unmark_dirty(self, conn_id: int) -> None:
        for dirty in self._dirty_sets.values():
            dirty.discard(conn_id)


class SpatialDecisionServicer:
    def __init__(self, auth_token: Optional[str] = None):
        self.auth_token = auth_token
        # Guards state swap only; step traffic serializes on the state's
        # own lock so Configure never queues behind a slow device step.
        self._swap_lock = threading.Lock()
        self._state: Optional[_EngineState] = None

    @property
    def engine(self):
        state = self._state
        return state.engine if state is not None else None

    # ---- auth --------------------------------------------------------

    def _check_auth(self, context) -> None:
        if not self.auth_token:
            return
        import hmac

        meta = dict(context.invocation_metadata() or ())
        if not hmac.compare_digest(
            meta.get(AUTH_METADATA_KEY, ""), self.auth_token
        ):
            import grpc

            context.abort(grpc.StatusCode.UNAUTHENTICATED,
                          "missing or wrong x-chtpu-auth token")

    # ---- rpc handlers ------------------------------------------------

    def configure(self, request: ConfigRequest, context) -> Empty:
        self._check_auth(context)
        from .engine import SpatialEngine
        from .spatial_ops import GridSpec
        from ..parallel.mesh import mesh_from_config

        try:
            mesh = mesh_from_config(
                request.meshDevices, request.meshHosts or 1
            )
        except ValueError as e:
            import grpc

            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        engine = SpatialEngine(
            GridSpec(
                offset_x=request.worldOffsetX,
                offset_z=request.worldOffsetZ,
                cell_w=request.gridWidth,
                cell_h=request.gridHeight,
                cols=request.gridCols,
                rows=request.gridRows,
            ),
            entity_capacity=request.entityCapacity or (1 << 17),
            query_capacity=request.queryCapacity or (1 << 12),
            sub_capacity=request.subCapacity or (1 << 16),
            mesh=mesh,
        )
        with self._swap_lock:
            self._state = _EngineState(engine)
        logger.info(
            "configured engine: %dx%d grid, %d entity slots, mesh=%s",
            request.gridCols, request.gridRows,
            request.entityCapacity or (1 << 17),
            f"{request.meshDevices}dev" if request.meshDevices else "none",
        )
        return Empty()

    def _current_state(self, context) -> _EngineState:
        with self._swap_lock:
            state = self._state
        if state is None:
            import grpc

            context.abort(grpc.StatusCode.FAILED_PRECONDITION, "not configured")
        return state

    def step(self, request: StepRequest, context) -> StepResponse:
        self._check_auth(context)
        state = self._current_state(context)
        # One dirty set per unary caller: the x-chtpu-caller metadata if
        # the gateway sends one, else the peer address. TTL-pruned in
        # _EngineState.dirty_for.
        meta = dict(context.invocation_metadata() or ())
        caller = ("unary", meta.get(CALLER_METADATA_KEY) or context.peer())
        try:
            with state.lock:
                return self._do_step(state, request, caller)
        except _StepValidationError as e:
            import grpc

            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))

    def step_stream(self, request_iterator, context):
        """One response per request; same semantics as Step, except a
        malformed request answers in-band (StepResponse.error) instead of
        killing the pipeline with its in-flight steps."""
        self._check_auth(context)
        caller = object()  # one dirty set per stream, dropped at stream end
        state = None
        try:
            for request in request_iterator:
                state = self._current_state(context)
                try:
                    # Yield OUTSIDE the lock: a generator suspends at
                    # yield, and a stalled stream consumer must not hold
                    # the engine lock against unary steps/other streams.
                    with state.lock:
                        resp = self._do_step(state, request, caller,
                                             pinned=True)
                except _StepValidationError as e:
                    resp = StepResponse(engineNowMs=request.nowMs,
                                        error=str(e))
                yield resp
        finally:
            if state is not None:
                with state.lock:
                    state.drop_caller(caller)

    # ---- the decision pass -------------------------------------------

    def _do_step(self, state: _EngineState, request: StepRequest,
                 caller: object, pinned: bool = False) -> StepResponse:
        eng = state.engine
        dirty = state.dirty_for(caller, pinned=pinned)
        for up in request.updates:
            eng.update_entity(up.entityId, up.x, up.y, up.z)
        for eid in request.removedEntityIds:
            eng.remove_entity(eid)
        for q in request.queries:
            if q.kind == AOI_SPOTS:
                if len(q.spotX) != len(q.spotZ):
                    raise _StepValidationError(
                        f"spotX/spotZ length mismatch "
                        f"({len(q.spotX)} vs {len(q.spotZ)})"
                    )
                eng.set_spots_query(
                    q.connId, list(zip(q.spotX, q.spotZ)), list(q.spotDists)
                )
                state.mark_dirty(q.connId)
                continue
            direction = (q.dirX, q.dirZ)
            if direction == (0.0, 0.0):
                direction = (1.0, 0.0)  # unset; a zero vector is invalid
            eng.set_query(
                q.connId, q.kind, (q.centerX, q.centerZ),
                (q.extentX, q.extentZ), direction, q.angle,
            )
            state.mark_dirty(q.connId)
        for conn_id in request.removedQueryConnIds:
            eng.remove_query(conn_id)
            state.unmark_dirty(conn_id)
        sub_map = state.sub_map
        for sub in request.addSubscriptions:
            sub_map[sub.subId] = eng.add_subscription(
                sub.fanOutIntervalMs, sub.firstDueMs
            )
        for sub_id in request.removeSubIds:
            slot = sub_map.pop(sub_id, None)
            if slot is not None:
                eng.remove_subscription(slot)

        now_ms = request.nowMs or eng.now_ms()
        result = eng.tick(now_ms)

        resp = StepResponse(engineNowMs=now_ms)
        resp.handoverCount = int(result["handover_count"])
        for entity_id, src, dst in eng.handover_list(result):
            resp.handovers.add(entityId=entity_id, srcCell=src, dstCell=dst)
        resp.cellCounts.extend(
            np.asarray(result["cell_counts"]).astype(np.uint32).tolist()
        )
        # Delta interest: AOI masks are a pure function of query geometry,
        # so only changed queries need recomputation/transfer — step cost
        # is flat in the standing query population.
        if request.fullInterest:
            report_conns = list(eng._q_of_conn.keys())
        else:
            report_conns = [c for c in dirty if c in eng._q_of_conn]
        if report_conns:
            interest = np.asarray(result["interest"])
            dist = np.asarray(result["dist"])
            for conn_id in report_conns:
                row = eng._q_of_conn[conn_id]
                cells = np.nonzero(interest[row])[0]
                ir = resp.interests.add(connId=conn_id)
                ir.cells.extend(cells.astype(np.uint32).tolist())
                ir.dists.extend(dist[row][cells].astype(np.uint32).tolist())
        dirty.clear()
        due = np.unpackbits(np.asarray(result["due_packed"]))
        slot_to_sub = {slot: sub_id for sub_id, slot in sub_map.items()}
        for slot in np.nonzero(due[: eng.sub_capacity])[0]:
            sub_id = slot_to_sub.get(int(slot))
            if sub_id is not None:
                resp.dueSubIds.append(sub_id)
        return resp


def create_server(port: int = 50051, max_workers: int = 4,
                  auth_token: Optional[str] = None):
    """Build (but don't start) the gRPC server; returns
    (server, servicer, bound_port). ``auth_token`` defaults to the
    CHTPU_SIDECAR_TOKEN env var; empty = no auth."""
    import grpc

    if auth_token is None:
        auth_token = os.environ.get("CHTPU_SIDECAR_TOKEN", "")
    servicer = SpatialDecisionServicer(auth_token=auth_token or None)
    handlers = grpc.method_handlers_generic_handler(
        SERVICE_NAME,
        {
            "Configure": grpc.unary_unary_rpc_method_handler(
                servicer.configure,
                request_deserializer=ConfigRequest.FromString,
                response_serializer=Empty.SerializeToString,
            ),
            "Step": grpc.unary_unary_rpc_method_handler(
                servicer.step,
                request_deserializer=StepRequest.FromString,
                response_serializer=StepResponse.SerializeToString,
            ),
            "StepStream": grpc.stream_stream_rpc_method_handler(
                servicer.step_stream,
                request_deserializer=StepRequest.FromString,
                response_serializer=StepResponse.SerializeToString,
            ),
        },
    )
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=max_workers))
    server.add_generic_rpc_handlers((handlers,))
    bound = server.add_insecure_port(f"[::]:{port}")
    if bound == 0:
        raise OSError(f"failed to bind sidecar port {port}")
    return server, servicer, bound


class SpatialDecisionClient:
    """Typed client for gateways written in Python (external gateways use
    the proto schema directly).

    Unary calls are hardened for the gateway tick loop: every call
    carries a deadline (a hung sidecar must never wedge the tick
    forever), and transient failures retry with deterministic
    exponential backoff before surfacing. Retryable codes are
    per-method: Configure is idempotent, so a timed-out call retries
    safely; Step is NOT retried on DEADLINE_EXCEEDED — a step that
    executed server-side but whose response timed out has already
    drained this caller's dirty set and allocated any requested
    subscription slots, so replaying it would lose delta-interest
    updates and leak slots. StepStream is not retried at all: a broken
    stream loses its per-caller delta state, so the caller must reopen
    and accept the automatic full resync."""

    # grpc codes considered transient per method; resolved lazily
    # (grpc import).
    _RETRYABLE = {
        "Configure": ("UNAVAILABLE", "DEADLINE_EXCEEDED"),
        "Step": ("UNAVAILABLE",),  # non-idempotent: see class docstring
    }

    def __init__(self, target: str = "127.0.0.1:50051",
                 auth_token: Optional[str] = None,
                 timeout_s: float = 5.0, max_retries: int = 3,
                 backoff_s: float = 0.1):
        import grpc

        self.target = target
        self.timeout_s = timeout_s
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self._channel = grpc.insecure_channel(target)
        meta = [(CALLER_METADATA_KEY, uuid.uuid4().hex)]
        if auth_token:
            meta.append((AUTH_METADATA_KEY, auth_token))
        self._metadata = tuple(meta)
        self._configure = self._channel.unary_unary(
            f"/{SERVICE_NAME}/Configure",
            request_serializer=ConfigRequest.SerializeToString,
            response_deserializer=Empty.FromString,
        )
        self._step = self._channel.unary_unary(
            f"/{SERVICE_NAME}/Step",
            request_serializer=StepRequest.SerializeToString,
            response_deserializer=StepResponse.FromString,
        )
        self._step_stream = self._channel.stream_stream(
            f"/{SERVICE_NAME}/StepStream",
            request_serializer=StepRequest.SerializeToString,
            response_deserializer=StepResponse.FromString,
        )

    def _call_with_retry(self, method_name: str, fn, request):
        """Deadline + deterministic exponential backoff on transient
        codes. Deterministic (no jitter) on purpose: chaos replays must
        see the same retry schedule."""
        import grpc

        retryable = tuple(
            getattr(grpc.StatusCode, c)
            for c in self._RETRYABLE.get(method_name, ())
        )
        delay = self.backoff_s
        attempt = 0
        while True:
            try:
                return fn(request, metadata=self._metadata,
                          timeout=self.timeout_s)
            except grpc.RpcError as e:
                code = e.code() if hasattr(e, "code") else None
                if code not in retryable or attempt >= self.max_retries:
                    raise
                attempt += 1
                try:
                    from ..core import metrics

                    metrics.sidecar_call_retries.labels(
                        method=method_name
                    ).inc()
                except Exception:
                    pass
                logger.warning(
                    "sidecar %s transient failure (%s); retry %d/%d in %.2fs",
                    method_name, code, attempt, self.max_retries, delay,
                )
                time.sleep(delay)
                delay *= 2

    def configure(self, **kwargs) -> None:
        self._call_with_retry(
            "Configure", self._configure, ConfigRequest(**kwargs)
        )

    def step(self, request: StepRequest) -> StepResponse:
        return self._call_with_retry("Step", self._step, request)

    def step_stream(self, request_iterator):
        """Returns the response iterator for a bidirectional pipeline."""
        return self._step_stream(request_iterator, metadata=self._metadata)

    def close(self) -> None:
        self._channel.close()


def main() -> None:
    import argparse

    p = argparse.ArgumentParser(description="channeld-tpu spatial decision sidecar")
    p.add_argument("--port", type=int, default=50051)
    p.add_argument("--auth-token", type=str, default=None,
                   help="shared secret; defaults to $CHTPU_SIDECAR_TOKEN")
    args = p.parse_args()
    from ..utils.devices import place_compile_cache

    place_compile_cache()
    server, _, bound = create_server(args.port, auth_token=args.auth_token)
    server.start()
    logger.info("spatial decision sidecar listening on :%d", bound)
    server.wait_for_termination()


if __name__ == "__main__":
    main()
