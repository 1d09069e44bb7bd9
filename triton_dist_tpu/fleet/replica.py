"""Fleet replica: one ``InferenceServer`` served over ``/fleet/*`` routes.

:class:`ReplicaService` adapts a running :class:`InferenceServer` to the
router's wire protocol by mounting JSON routes on the process's
introspection endpoint (``runtime.introspect.register_json_route``):

``POST /fleet/submit``     admit ``{prompt, max_new, priority?, deadlines?}``
``POST /fleet/resume``     admit mid-stream with a token history (migration)
``POST /fleet/stream``     batched positional poll: ``{reqs: [[id, from]..]}``
``POST /fleet/placement``  warm-prefix + load hint for ``{prompt}``
``POST /fleet/cancel``     cancel ``{req_id}`` (drain-side of a migration)
``POST /fleet/kv_export``  pack a parked handoff's KV blocks: ``{req_id}``
``POST /fleet/kv_import``  admit with wire KV: ``{prompt, max_new, tokens,
                           kv}`` (the decode-pool half of a handoff)
``POST /fleet/kv_release`` drop a parked handoff's refs: ``{req_id}``
``POST /fleet/drain``      enter drain mode (rolling rebuild)
``GET  /fleet/status``     ready / draining / drained / occupancy
``GET  /fleet/journal``    flush + export the write-ahead journal records
``GET  /fleet/trace/<id>`` this replica's span ring for one trace (hex or
                           decimal id) — the router fetches these to merge
                           a fleet request's cross-process timeline

Trace propagation: ``submit``/``resume`` bodies may carry a ``"trace"``
carrier (``tracing.inject`` W3C-traceparent shape). It is extracted and
threaded into the server, so the replica's whole serving span chain
(queue wait → prefill → decode chunks → stream) parents under the
router's placement span in ONE fleet-wide trace. A missing or malformed
carrier falls back to a local trace — propagation can never break
admission.

Wire hardening (the structured-error contract the router's ``_http``
counts on): a non-object body or missing/garbage fields → 400
``{"error": ...}``, wrong verb → 405, unknown ``/fleet/`` path → 404 —
never a replica-side stack trace.

Streams are delivered by ABSOLUTE token position: the service mirrors each
request's ``tokens`` history into a poll buffer, and ``/fleet/stream``
returns the slice from the caller's position. That makes delivery
idempotent under router retries and makes migration dedupe trivial — the
router polls from "tokens I have delivered" wherever the request lives.

``python -m triton_dist_tpu.fleet.replica`` boots one replica subprocess:
an env-configured model + engine + server (``TDT_REPLICA_*`` knobs below),
the introspection endpoint on an ephemeral port (``TDT_HTTP_PORT=0``,
reported through ``TDT_HTTP_PORT_FILE``), and a serve-forever loop that a
SIGTERM converts into a draining shutdown. The built-in model builder is
the world-1 test/bench replica; a production fleet wires its own model and
reuses :class:`ReplicaService` unchanged.
"""

from __future__ import annotations

import os
import threading
import time

from triton_dist_tpu.runtime import introspect, tracing
from triton_dist_tpu.runtime.utils import get_int_env, tdt_log


class ReplicaService:
    """Mount the ``/fleet/*`` routes over one :class:`InferenceServer`.

    Handlers run on endpoint threads; everything they touch is either
    thread-safe server API (``submit``/``resume``/``cancel`` and the
    read-only hint/status views) or this service's own lock-protected
    poll buffers, fed from the serving loop via request callbacks.
    """

    PREFIX = "/fleet/"

    def __init__(self, server):
        self.server = server
        self._lock = threading.Lock()
        #: req_id -> {"tokens": [...], "done": bool, "reason": str | None}.
        #: ``tokens`` mirrors the request's full history (seed included for
        #: resumed requests) so stream positions are absolute.
        self._streams: dict[int, dict] = {}
        for name, fn, methods in (
            ("submit", self._r_submit, ("POST",)),
            ("resume", self._r_resume, ("POST",)),
            ("stream", self._r_stream, ("POST",)),
            ("placement", self._r_placement, ("POST",)),
            ("cancel", self._r_cancel, ("POST",)),
            ("kv_export", self._r_kv_export, ("POST",)),
            ("kv_import", self._r_kv_import, ("POST",)),
            ("kv_release", self._r_kv_release, ("POST",)),
            ("drain", self._r_drain, ("GET", "POST")),
            ("status", self._r_status, ("GET", "POST")),
            ("journal", self._r_journal, ("GET", "POST")),
            ("trace/", self._r_trace, ("GET",)),
        ):
            introspect.register_json_route(self.PREFIX + name, fn,
                                           methods=methods)

    def close(self) -> None:
        introspect.clear_json_routes(self.PREFIX)

    # ------------------------------------------------------ stream mirroring
    def _on_token(self, req, token, index) -> None:
        # Serving-loop thread. ``req.tokens`` already holds everything up to
        # ``index``, so extending from it heals any entry created late (the
        # submit response raced the first prefill) and pre-seeds resumed
        # histories without a separate registration step.
        with self._lock:
            st = self._streams.setdefault(
                req.req_id, {"tokens": [], "done": False, "reason": None}
            )
            toks = st["tokens"]
            if len(toks) <= index:
                toks.extend(int(t) for t in req.tokens[len(toks):])

    def _on_finish(self, req) -> None:
        with self._lock:
            st = self._streams.setdefault(
                req.req_id, {"tokens": [], "done": False, "reason": None}
            )
            toks = st["tokens"]
            if len(toks) < len(req.tokens):
                toks.extend(int(t) for t in req.tokens[len(toks):])
            st["done"] = True
            st["reason"] = req.finish_reason

    def _admit_response(self, req) -> tuple[int, dict]:
        from triton_dist_tpu.serving import RequestState

        if req.state is not RequestState.QUEUED:
            return 200, {
                "req_id": req.req_id,
                "state": req.state.value,
                "reject_reason": req.reject_reason,
            }
        with self._lock:
            st = self._streams.setdefault(
                req.req_id, {"tokens": [], "done": False, "reason": None}
            )
            toks = st["tokens"]
            if len(toks) < len(req.tokens):
                toks.extend(int(t) for t in req.tokens[len(toks):])
        return 200, {"req_id": req.req_id, "state": req.state.value}

    # --------------------------------------------------------------- routes
    @staticmethod
    def _body_error(body, *required: str) -> str | None:
        """The structured-400 gate every body-taking route runs first."""
        if not isinstance(body, dict):
            return "JSON object body required"
        missing = [k for k in required if k not in body]
        if missing:
            return f"missing field(s): {', '.join(missing)}"
        return None

    def _r_submit(self, method, query, body) -> tuple[int, dict]:
        err = self._body_error(body, "prompt", "max_new")
        if err:
            return 400, {"error": err}
        try:
            req = self.server.submit(
                body["prompt"], int(body["max_new"]),
                on_token=self._on_token, on_finish=self._on_finish,
                priority=int(body.get("priority", 1)),
                tenant=str(body.get("tenant", "default")),
                weight=float(body.get("weight", 1.0)),
                ttft_deadline_s=body.get("ttft_deadline_s"),
                deadline_s=body.get("deadline_s"),
                trace_ctx=tracing.extract(body.get("trace")),
                prefill_only=bool(body.get("prefill_only", False)),
            )
        except (TypeError, ValueError) as e:
            return 400, {"error": f"bad field value: {e}"}
        return self._admit_response(req)

    def _r_resume(self, method, query, body) -> tuple[int, dict]:
        err = self._body_error(body, "prompt", "max_new")
        if err:
            return 400, {"error": err}
        try:
            req = self.server.resume(
                body["prompt"], int(body["max_new"]), body.get("tokens", []),
                on_token=self._on_token, on_finish=self._on_finish,
                priority=int(body.get("priority", 1)),
                tenant=str(body.get("tenant", "default")),
                weight=float(body.get("weight", 1.0)),
                ttft_deadline_s=body.get("ttft_deadline_s"),
                deadline_s=body.get("deadline_s"),
                trace_ctx=tracing.extract(body.get("trace")),
            )
        except (TypeError, ValueError) as e:
            return 400, {"error": f"bad field value: {e}"}
        return self._admit_response(req)

    def _r_stream(self, method, query, body) -> tuple[int, dict]:
        err = self._body_error(body)
        if err:
            return 400, {"error": err}
        reqs = body.get("reqs", [])
        if not isinstance(reqs, list) or any(
            not isinstance(it, (list, tuple)) or len(it) != 2 for it in reqs
        ):
            return 400, {"error": "reqs must be a list of [req_id, from]"}
        out = {}
        try:
            with self._lock:
                for rid, frm in reqs:
                    st = self._streams.get(int(rid))
                    if st is None:
                        out[str(rid)] = {"tokens": [], "done": False,
                                         "reason": None, "unknown": True}
                        continue
                    out[str(rid)] = {
                        "tokens": st["tokens"][max(int(frm), 0):],
                        "done": st["done"],
                        "reason": st["reason"],
                    }
        except (TypeError, ValueError) as e:
            return 400, {"error": f"bad field value: {e}"}
        return 200, {"streams": out}

    def _r_placement(self, method, query, body) -> tuple[int, dict]:
        err = self._body_error(body)
        if err:
            return 400, {"error": err}
        try:
            return 200, self.server.placement_info(
                body.get("prompt", []),
                tenant=str(body.get("tenant", "default")),
            )
        except (TypeError, ValueError) as e:
            return 400, {"error": f"bad field value: {e}"}

    def _r_cancel(self, method, query, body) -> tuple[int, dict]:
        err = self._body_error(body, "req_id")
        if err:
            return 400, {"error": err}
        try:
            return 200, {"cancelled": self.server.cancel(int(body["req_id"]))}
        except (TypeError, ValueError) as e:
            return 400, {"error": f"bad field value: {e}"}

    def _r_kv_export(self, method, query, body) -> tuple[int, dict]:
        """Pack a parked handoff's prefilled KV blocks into the wire blob
        (``disagg.kv_transfer`` v1). 404 when nothing is parked — the
        router's cue to fall back to journal re-derivation."""
        err = self._body_error(body, "req_id")
        if err:
            return 400, {"error": err}
        try:
            return 200, {"kv": self.server.export_kv(int(body["req_id"]))}
        except KeyError as e:
            return 404, {"error": str(e)}
        except (TypeError, ValueError) as e:
            return 400, {"error": f"bad field value: {e}"}

    def _r_kv_import(self, method, query, body) -> tuple[int, dict]:
        """Admit a request whose prefill KV arrives in the body (the
        decode-pool half of a disaggregated handoff)."""
        err = self._body_error(body, "prompt", "max_new", "tokens", "kv")
        if err:
            return 400, {"error": err}
        try:
            req = self.server.import_kv(
                body["prompt"], int(body["max_new"]), body["tokens"],
                body["kv"],
                on_token=self._on_token, on_finish=self._on_finish,
                priority=int(body.get("priority", 1)),
                tenant=str(body.get("tenant", "default")),
                weight=float(body.get("weight", 1.0)),
                ttft_deadline_s=body.get("ttft_deadline_s"),
                deadline_s=body.get("deadline_s"),
                trace_ctx=tracing.extract(body.get("trace")),
            )
        except (TypeError, ValueError) as e:
            return 400, {"error": f"bad field value: {e}"}
        return self._admit_response(req)

    def _r_kv_release(self, method, query, body) -> tuple[int, dict]:
        err = self._body_error(body, "req_id")
        if err:
            return 400, {"error": err}
        try:
            return 200, {
                "released": self.server.release_handoff(int(body["req_id"]))
            }
        except (TypeError, ValueError) as e:
            return 400, {"error": f"bad field value: {e}"}

    def _r_trace(self, method, query, body, rest="") -> tuple[int, dict]:
        """``GET /fleet/trace/<id>``: this process's span ring for one
        trace — what the router merges into the fleet-wide timeline. The id
        is the 32-hex traceparent form (canonical) or decimal."""
        tid = _parse_trace_id(rest)
        if tid is None:
            return 400, {"error": f"bad trace id {rest!r} "
                                  "(want 32-hex or decimal)"}
        sps = tracing.spans(tid, include_open=True)
        if not sps:
            return 404, {"error": f"unknown trace {rest!r}"}
        return 200, {
            "trace_id_hex": f"{tid:032x}",
            "pid": os.getpid(),
            "spans": sps,
        }

    def _r_drain(self, method, query, body) -> tuple[int, dict]:
        self.server.drain_begin()
        return 200, self._status()

    def _r_status(self, method, query, body) -> tuple[int, dict]:
        return 200, self._status()

    def _r_journal(self, method, query, body) -> tuple[int, dict]:
        return 200, {
            "records": self.server.journal_records(),
            "path": (
                self.server._journal.path
                if self.server._journal is not None else None
            ),
        }

    def _status(self) -> dict:
        s = self.server
        return {
            "ready": not (s.draining or s._shutdown),
            "draining": s.draining,
            "drained": s.drained,
            "occupancy": s.scheduler.occupancy(),
            "queue_depth": s.scheduler.queue_depth(),
            "backend": s.engine.backend,
            "role": s.role,
            "parked_handoffs": len(s._handoffs),
            "pid": os.getpid(),
        }


#: Shared with the router's ``/fleet/trace/<id>`` federation route.
_parse_trace_id = tracing.parse_trace_id


# ------------------------------------------------------- subprocess entry


def build_server():
    """Env-configured world-1 replica: model + engine + journaled server.

    ``TDT_REPLICA_PRESET`` (default ``test-dense``), ``TDT_REPLICA_BACKEND``
    (default ``xla``), ``TDT_REPLICA_MAX_LEN`` (default 32) and
    ``TDT_REPLICA_SEED`` (default 1) pick the model; every replica of a
    fleet must share preset/seed/backend so greedy decoding regenerates
    migrated streams byte-identically. ``TDT_PP_STAGES`` > 1 builds the
    replica over a ``pp×tp`` CPU mesh of that many pipeline stages (model
    init is mesh-independent, so PP replicas stay byte-compatible with
    world-1 peers). Slots/chunk/journal ride the usual ``TDT_SERVE_*`` /
    ``TDT_JOURNAL_DIR`` knobs.
    """
    import jax

    from triton_dist_tpu.models import PRESETS, DenseLLM, Engine
    from triton_dist_tpu.runtime.mesh import initialize_distributed
    from triton_dist_tpu.runtime.platform import (
        cpu_mesh,
        enable_compile_cache,
        use_cpu_devices,
    )
    from triton_dist_tpu.serving import InferenceServer

    # Every replica of a fleet compiles the same programs: the persistent
    # cache lets a respawn or a scale-up boot from what its peers compiled.
    enable_compile_cache()
    preset = os.environ.get("TDT_REPLICA_PRESET", "test-dense")
    backend = os.environ.get("TDT_REPLICA_BACKEND", "xla")
    max_len = get_int_env("TDT_REPLICA_MAX_LEN", 32)
    seed = get_int_env("TDT_REPLICA_SEED", 1)
    pp = get_int_env("TDT_PP_STAGES", 1)
    if pp > 1:
        use_cpu_devices(max(pp, 2))
        m = cpu_mesh((pp, 1), ("pp", "tp"))
        ctx = initialize_distributed(
            devices=list(m.devices.flat), axis_names=("pp", "tp"),
            axis_sizes=(pp, 1), set_default=False,
        )
    else:
        m = cpu_mesh((1,), ("tp",))
        ctx = initialize_distributed(
            devices=list(m.devices.flat), axis_names=("tp",),
            set_default=False,
        )
    model = DenseLLM(PRESETS[preset], ctx, key=jax.random.PRNGKey(seed))
    engine = Engine(model, backend=backend, max_len=max_len)
    return InferenceServer(engine)


def main() -> int:
    # A fleet replica is pointless without its endpoint: default to an
    # ephemeral port (the router reads the actual one via the port file).
    os.environ.setdefault("TDT_HTTP_PORT", "0")
    server = build_server()
    if server._introspect is None:
        tdt_log("[fleet.replica] introspection endpoint failed to start",
                level="error")
        return 1
    service = ReplicaService(server)
    server.install_signal_handlers()
    tdt_log(
        f"[fleet.replica] ready pid={os.getpid()} "
        f"port={server._introspect.port} backend={server.engine.backend}"
    )
    try:
        # Serve forever (InferenceServer.run returns on an idle queue):
        # SIGTERM sets the shutdown flag, which we convert into a draining
        # shutdown below — the journal holds whatever a kill -9 would strand.
        while not server._shutdown_requested:
            if not server.step():
                time.sleep(0.005)
    except KeyboardInterrupt:
        pass
    finally:
        service.close()
        server.shutdown(drain=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
