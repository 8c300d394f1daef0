"""LLMServer + LLMRouter Serve deployments (OpenAI-compatible).

Reference parity: llm/_internal/serve/deployments/llm/llm_server.py:415
(LLMServer wrapping the engine) and deployments/routers/router.py
(LLMRouter exposing /v1/chat/completions, /v1/completions, /v1/models).
The engine here is the TPU-native one (engine.py), not external vLLM.

The server pumps engine.step() on a background asyncio task; each request
registers an asyncio.Queue that tokens stream into, so concurrent HTTP
requests share the continuously-batched decode loop.
"""

from __future__ import annotations

import asyncio
import functools
import time
import uuid
from typing import Any, Dict, List, Optional

import jax

from ...util.compile_cache import ensure_compile_cache
from .engine import EngineConfig, InferenceEngine, Request, SamplingParams
from .tokenizer import load_tokenizer

# max_tokens when the body omits it — ALSO the value the fleet pins on
# a stream before its first dispatch (failover continuations decrement
# it), so it lives here once and the fleet imports it
DEFAULT_MAX_TOKENS = 32

# body keys minted by the fleet ingress (ISSUE 7/9/12 plumbing): every
# public ingress must strip client-supplied values — a forged
# `_request_id` could replay/abort another request, `_continue_tokens`
# injects raw token ids, `_deadline_epoch` bypasses `deadline_s`, and
# `_session` would inject raw KV pages into the pool (ISSUE 12). One
# canonical list; the fleet imports it too.
INTERNAL_BODY_KEYS = ("_request_id", "_trace", "_deadline_epoch",
                      "_continue_tokens", "_token_offset",
                      "_session", "_resume_offset", "_chat",
                      "_tenant", "_lane")


def parse_since(raw: Any) -> "int | None":
    """`?since=<seq>` cursor parsing (ISSUE 20 satellite), shared by
    this ingress and the fleet's: absent or malformed → None (full
    ring — a bad cursor must degrade to the legacy shape, never
    500)."""
    if raw is None:
        return None
    try:
        return int(raw)
    except (TypeError, ValueError):
        return None


class LLMServerImpl:
    """The deployment class body (decorated at app-build time)."""

    def __init__(self, llm_config: Dict[str, Any]):
        ensure_compile_cache()
        self._config = dict(llm_config)
        engine_kwargs = dict(self._config.get("engine_kwargs") or {})
        self.model_id = self._config.get("model_id", "default")
        # Prometheus samples tag per model (ISSUE 5) unless the
        # engine_kwargs pin an explicit tag
        engine_kwargs.setdefault("metrics_model_id", self.model_id)
        # fleet identity (ISSUE 6): the fleet deployment builder
        # injects metrics_replica_id so this replica's series and
        # fleet_stats() rows carry its id; standalone servers stay ""
        self.replica_id = str(
            engine_kwargs.get("metrics_replica_id") or "")
        self.engine = InferenceEngine(EngineConfig(
            model=self._config.get("model_source", "debug"),
            **engine_kwargs))
        self.tokenizer = load_tokenizer(
            self._config.get("tokenizer_source"),
            vocab_size=self.engine.model_cfg.vocab_size)
        # LoRA adapters declared in the config load at construction
        # (reference parity: serve LLM LoRA multiplex config); more can
        # be added live via the register_lora deployment method
        if self._config.get("lora_adapters"):
            self.engine.register_loras(
                dict(self._config["lora_adapters"]))
        self._queues: Dict[str, asyncio.Queue] = {}
        self._pump: Optional[asyncio.Task] = None
        self._wake: Optional[asyncio.Event] = None

    # -- engine pump --------------------------------------------------------
    def _ensure_pump(self) -> None:
        if self._pump is None or self._pump.done():
            self._wake = asyncio.Event()
            self._pump = asyncio.create_task(self._pump_loop())

    async def _pump_loop(self) -> None:
        while True:
            if not self.engine.has_work():
                self._wake.clear()
                await self._wake.wait()
            # run the blocking device step off the event loop so request
            # handlers/health checks stay responsive
            touched = await asyncio.get_running_loop().run_in_executor(
                None, self.engine.step)
            # the pump's share of the time between two ticks, as a span
            # on the profiler's clock beside the engine's own
            with jax.profiler.TraceAnnotation("server.deliver",
                                              touched=len(touched)):
                for req in touched:
                    q = self._queues.get(req.request_id)
                    if q is not None:
                        # a deadline expiry in the waiting queue
                        # finishes a request that never produced a
                        # token — the event must still reach its stream
                        tok = (req.output_tokens[-1]
                               if req.output_tokens else None)
                        q.put_nowait((tok, req.finished,
                                      req.finish_reason))
            await asyncio.sleep(0)

    def _abort_off_loop(self, rid: str) -> None:
        """Fire an engine abort WITHOUT blocking the event loop:
        abort serializes against step() (engine._step_lock), and a
        step is a whole device dispatch (a compiling one takes
        seconds) — awaiting it in a stream's finally would
        freeze every other coroutine (and an async generator being
        closed cannot await at all). Fire-and-forget on the executor;
        abort never raises for an unknown/finished request, but a
        broken engine invariant (fold assert, OOM in the rebuild)
        must reach the logs, not die with the discarded future."""
        def _surface(fut):
            exc = fut.exception()
            if exc is not None:
                import logging
                logging.getLogger(__name__).exception(
                    "engine.abort(%s) failed", rid, exc_info=exc)

        try:
            asyncio.get_running_loop().run_in_executor(
                None, self.engine.abort, rid
            ).add_done_callback(_surface)
        except RuntimeError:        # no running loop (teardown)
            self.engine.abort(rid)

    # -- generation ---------------------------------------------------------
    @staticmethod
    def _trace_of(body: Dict[str, Any]):
        """Pop the fleet ingress's trace plumbing off the body (ISSUE
        7): `_request_id` keeps ONE id across ingress, router, and
        engine; `_trace` is the minted span context the telemetry
        timeline tags its lifecycle events with (and binds the
        Perfetto flow arrow to). Both public ingresses control these
        keys — the fleet ingress overwrites them with minted values
        and LLMRouterImpl strips client-supplied ones — so what
        arrives here is trusted plumbing, not client input."""
        rid = body.pop("_request_id", None)
        trace = body.pop("_trace", None)
        return (str(rid) if rid else None,
                dict(trace) if isinstance(trace, dict) else None)

    @staticmethod
    def _deadline_of(body: Dict[str, Any]) -> "float | None":
        """Pop the request deadline (ISSUE 9) as an absolute MONOTONIC
        instant: `_deadline_epoch` (absolute wall clock, minted at the
        fleet ingress so it survives process hops) wins over a direct
        client `deadline_s` (seconds from now). The engine aborts the
        request at the first fold boundary past it."""
        ep = body.pop("_deadline_epoch", None)
        if ep is not None:
            return time.monotonic() + (float(ep) - time.time())
        ds = body.get("deadline_s")
        if ds is not None:
            return time.monotonic() + float(ds)
        return None

    def _prompt_tokens(self, body: Dict[str, Any],
                       chat: bool) -> List[int]:
        """Encode the request's prompt — plus `_continue_tokens`, the
        failover continuation's already-emitted output ids (ISSUE 9):
        the fleet re-dispatches a severed stream as the ORIGINAL
        prompt with the delivered tokens appended, so the new replica
        re-prefills (cheaply, via the prefix cache) and resumes the
        exact token sequence."""
        if chat:
            prompt = self.tokenizer.apply_chat_template(
                body.get("messages") or [])
        else:
            prompt = str(body.get("prompt") or "")
        toks = self.tokenizer.encode(prompt)
        cont = body.get("_continue_tokens")
        if cont:
            toks = toks + [int(t) for t in cont]
        return toks

    @staticmethod
    def _tenant_of(body: Dict[str, Any]) -> str:
        """Tenant identity for cost attribution (ISSUE 13): the fleet
        ingress mints `_tenant` at admission (from the OpenAI `user`
        field, "" for the default tenant); a standalone server reads
        the same client fields directly. "" = default tenant — its
        label is omitted from expositions."""
        t = body.pop("_tenant", None)
        if t is None:
            t = body.get("user") or body.get("tenant") or ""
        t = str(t)
        return "" if t == "default" else t

    @staticmethod
    def _lane_of(body: Dict[str, Any]) -> str:
        """Scheduling lane (ISSUE 14): the fleet's batch pump mints
        `_lane: "batch"` on the bodies it dispatches (a plumbing key
        — public ingresses strip client-supplied values, so a client
        cannot exempt itself from SLO accounting by forging it).
        Everything else is the interactive lane."""
        return ("batch" if body.pop("_lane", None) == "batch"
                else "interactive")

    @staticmethod
    def _priority_of(body: Dict[str, Any]) -> int:
        """Preemption priority (ISSUE 10, API extension): under page
        pressure the engine parks the LOWEST priority first. Clients
        (or the fleet's tenant tiers) pass `priority`; absent = 0."""
        try:
            return int(body.get("priority") or 0)
        except (TypeError, ValueError):
            return 0

    async def _generate(self, prompt_tokens: List[int],
                        params: SamplingParams,
                        lora: "str | None" = None,
                        rid: "str | None" = None,
                        trace: "Dict[str, str] | None" = None,
                        deadline: "float | None" = None,
                        priority: int = 0,
                        tenant: str = "",
                        lane: str = "interactive") -> Request:
        self._ensure_pump()
        # a rid already in flight (a client replaying another request's
        # `_request_id`) must not collide: the duplicate would overwrite
        # the live request's token queue and abort it on teardown —
        # fall back to a fresh id (the trace context still rides along)
        if not rid or rid in self._queues:
            rid = uuid.uuid4().hex[:16]
        req = Request(rid, prompt_tokens, params, lora=lora,
                      trace=trace, deadline=deadline,
                      priority=priority, tenant=tenant, lane=lane)
        q: asyncio.Queue = asyncio.Queue()
        self._queues[rid] = q
        try:
            # off-loop: add_request takes the step lock (racelint
            # RL002 — a mid-tick pump holds it for the whole dispatch,
            # and blocking here would stall every other stream)
            await asyncio.get_running_loop().run_in_executor(
                None, self.engine.add_request, req)
            self._wake.set()
            while True:
                _, finished, _ = await asyncio.wait_for(q.get(),
                                                        timeout=300)
                if finished:
                    return req
        finally:
            self._queues.pop(rid, None)
            if not req.finished:
                # caller gone (timeout/cancel): stop decoding for nobody
                self._abort_off_loop(rid)

    def _lora_for(self, body: Dict[str, Any]) -> "str | None":
        """LoRA multiplexing the vLLM way: requesting model=<adapter
        name> routes onto the base model + that adapter. An unknown
        model name is an ERROR (vLLM returns 404), not a silent
        base-model fallback."""
        model = body.get("model")
        if not model or model == self.model_id:
            return None
        if model in getattr(self.engine, "_lora_raw", {}):
            return model
        raise ValueError(
            f"unknown model {model!r} (base: {self.model_id!r}, "
            f"adapters: {sorted(getattr(self.engine, '_lora_raw', {}))})")

    def _sampling(self, body: Dict[str, Any]) -> SamplingParams:
        eos = getattr(self.tokenizer, "eos_id",
                      getattr(self.tokenizer, "eos_token_id", None))
        stop = (eos,) if eos is not None else ()
        seed = body.get("seed")          # OpenAI param; None derives
        return SamplingParams(           # from the request id
            max_tokens=int(body.get("max_tokens")
                           or DEFAULT_MAX_TOKENS),
            temperature=float(body.get("temperature") or 0.0),
            top_p=float(body.get("top_p") or 1.0),
            # OpenAI-API extensions every serving stack grew (vLLM/TGI)
            top_k=int(body.get("top_k") or 0),
            repetition_penalty=float(
                body.get("repetition_penalty") or 1.0),
            stop_token_ids=stop,
            seed=None if seed is None else int(seed))

    def _usage(self, toks: List[int], req: Request) -> Dict[str, Any]:
        """OpenAI usage block + the `cost` extension (ISSUE 13): the
        request's attribution receipt — analytic FLOPs/HBM bytes, KV
        page-ticks, queue/wall time shares — so a caller can see what
        its completion consumed, not just how many tokens it got."""
        usage = {
            "prompt_tokens": len(toks),
            "completion_tokens": len(req.output_tokens),
            "total_tokens": len(toks) + len(req.output_tokens),
        }
        attrib = getattr(self.engine, "attrib", None)
        if attrib is not None:
            rec = attrib.receipt(req.request_id)
            if rec is not None:
                usage["cost"] = rec.cost_block()
        return usage

    async def chat(self, body: Dict[str, Any]) -> Dict[str, Any]:
        rid, trace = self._trace_of(body)
        deadline = self._deadline_of(body)
        toks = self._prompt_tokens(body, chat=True)
        req = await self._generate(toks, self._sampling(body),
                                   lora=self._lora_for(body),
                                   rid=rid, trace=trace,
                                   deadline=deadline,
                                   priority=self._priority_of(body),
                                   tenant=self._tenant_of(body),
                                   lane=self._lane_of(body))
        text = self.tokenizer.decode(req.output_tokens)
        return {
            "id": f"chatcmpl-{req.request_id}",
            "object": "chat.completion",
            "created": int(time.time()),
            "model": self.model_id,
            "choices": [{
                "index": 0,
                "message": {"role": "assistant", "content": text},
                "finish_reason": req.finish_reason,
            }],
            "usage": self._usage(toks, req),
        }

    async def completions(self, body: Dict[str, Any]) -> Dict[str, Any]:
        rid, trace = self._trace_of(body)
        deadline = self._deadline_of(body)
        toks = self._prompt_tokens(body, chat=False)
        req = await self._generate(toks, self._sampling(body),
                                   lora=self._lora_for(body),
                                   rid=rid, trace=trace,
                                   deadline=deadline,
                                   priority=self._priority_of(body),
                                   tenant=self._tenant_of(body),
                                   lane=self._lane_of(body))
        return {
            "id": f"cmpl-{req.request_id}",
            "object": "text_completion",
            "created": int(time.time()),
            "model": self.model_id,
            "choices": [{
                "index": 0,
                "text": self.tokenizer.decode(req.output_tokens),
                "finish_reason": req.finish_reason,
            }],
            "usage": self._usage(toks, req),
        }

    @staticmethod
    def _settled(text: str) -> str:
        """`text` less what its next token may still change: a
        multi-byte character whose bytes have not all arrived decodes
        to U+FFFD at the tail, and a delta once sent cannot be taken
        back. A stream holds that tail until it completes or ends."""
        return text.rstrip("\ufffd")

    async def _generate_stream(self, prompt_tokens: List[int],
                               params: SamplingParams,
                               lora: "str | None" = None,
                               rid: "str | None" = None,
                               trace: "Dict[str, str] | None" = None,
                               deadline: "float | None" = None,
                               decode_ctx: "List[int] | None" = None,
                               priority: int = 0,
                               tenant: str = "",
                               lane: str = "interactive"):
        """Yield (new_tokens, text_delta, finished, finish_reason) as
        tokens land — token ids AND text per event, so both the SSE
        wrappers (text) and the fleet's failover relay (token-exact
        dedup, ISSUE 9) consume one stream.

        decode_ctx: tokens the CLIENT already holds (a failover
        continuation's `_continue_tokens`) — deltas are decoded with
        them as context, so a multi-byte character whose tokens span
        the failover boundary renders correctly instead of as two
        replacement characters."""
        self._ensure_pump()
        if not rid or rid in self._queues:   # see _generate: a replayed
            rid = uuid.uuid4().hex[:16]      # id must never collide
        req = Request(rid, prompt_tokens, params, lora=lora,
                      trace=trace, deadline=deadline,
                      priority=priority, tenant=tenant, lane=lane)
        q: asyncio.Queue = asyncio.Queue()
        self._queues[rid] = q
        ctx = list(decode_ctx or [])
        try:
            # off-loop: add_request takes the step lock (racelint
            # RL002 — a mid-tick pump holds it for the whole dispatch,
            # and blocking here would stall every other stream)
            await asyncio.get_running_loop().run_in_executor(
                None, self.engine.add_request, req)
            self._wake.set()
            n_sent = len(self._settled(self.tokenizer.decode(ctx)))
            n_toks = 0
            while True:
                _, finished, reason = await asyncio.wait_for(q.get(),
                                                             timeout=300)
                # decode incrementally: whole-prefix decode keeps
                # multi-byte tokenizations correct
                text = self.tokenizer.decode(ctx + req.output_tokens)
                if not finished:
                    text = self._settled(text)
                delta, n_sent = text[n_sent:], len(text)
                new = list(req.output_tokens[n_toks:])
                n_toks = len(req.output_tokens)
                if not new and not delta and not finished:
                    # a step that folds two ticks (a drain) touches a
                    # request twice; the first event already read both
                    # tokens, the second carries nothing new
                    continue
                yield new, delta, finished, reason
                if finished:
                    return
        finally:
            self._queues.pop(rid, None)
            if not req.finished:
                # stream abandoned mid-generation: free the slot + pages
                self._abort_off_loop(rid)

    async def chat_stream(self, body: Dict[str, Any]):
        """SSE chunks for stream=true chat completions (OpenAI format)."""
        import json
        rid, trace = self._trace_of(body)
        deadline = self._deadline_of(body)
        toks = self._prompt_tokens(body, chat=True)
        cid = f"chatcmpl-{uuid.uuid4().hex[:16]}"
        async for _, delta, finished, reason in self._generate_stream(
                toks, self._sampling(body), lora=self._lora_for(body),
                rid=rid, trace=trace, deadline=deadline,
                priority=self._priority_of(body),
                tenant=self._tenant_of(body),
                lane=self._lane_of(body)):
            if not delta and not finished:
                continue                 # no text yet: hold the chunk
            chunk = {
                "id": cid, "object": "chat.completion.chunk",
                "created": int(time.time()), "model": self.model_id,
                "choices": [{
                    "index": 0,
                    "delta": ({"content": delta} if delta else {}),
                    "finish_reason": reason if finished else None,
                }],
            }
            yield f"data: {json.dumps(chunk)}\n\n"
        yield "data: [DONE]\n\n"

    async def completions_stream(self, body: Dict[str, Any]):
        import json
        rid, trace = self._trace_of(body)
        deadline = self._deadline_of(body)
        toks = self._prompt_tokens(body, chat=False)
        cid = f"cmpl-{uuid.uuid4().hex[:16]}"
        async for _, delta, finished, reason in self._generate_stream(
                toks, self._sampling(body), lora=self._lora_for(body),
                rid=rid, trace=trace, deadline=deadline,
                priority=self._priority_of(body),
                tenant=self._tenant_of(body),
                lane=self._lane_of(body)):
            if not delta and not finished:
                continue
            chunk = {
                "id": cid, "object": "text_completion",
                "created": int(time.time()), "model": self.model_id,
                "choices": [{
                    "index": 0, "text": delta,
                    "finish_reason": reason if finished else None,
                }],
            }
            yield f"data: {json.dumps(chunk)}\n\n"
        yield "data: [DONE]\n\n"

    # -- token-structured streams (ISSUE 9 failover plane) ----------------
    async def _stream_tokens(self, body: Dict[str, Any], chat: bool):
        """Structured token chunks for the fleet's failover-aware SSE
        relay: {"i": index of the chunk's first output token, "toks":
        new token ids, "text": decoded delta, "finished", "reason",
        "model"}. `_token_offset` shifts the indices a continuation
        reports, so the fleet's dedup-by-token-index sees ONE
        monotone stream across replica failovers."""
        rid, trace = self._trace_of(body)
        deadline = self._deadline_of(body)
        toks = self._prompt_tokens(body, chat=chat)
        idx = int(body.get("_token_offset") or 0)
        cont = [int(t) for t in body.get("_continue_tokens") or []]
        async for new, delta, finished, reason in self._generate_stream(
                toks, self._sampling(body), lora=self._lora_for(body),
                rid=rid, trace=trace, deadline=deadline,
                decode_ctx=cont, priority=self._priority_of(body),
                tenant=self._tenant_of(body),
                lane=self._lane_of(body)):
            yield {"i": idx, "toks": list(new), "text": delta,
                   "finished": bool(finished),
                   "reason": reason if finished else None,
                   "model": self.model_id,
                   "prompt_tokens": len(toks)}
            idx += len(new)

    async def chat_stream_tokens(self, body: Dict[str, Any]):
        async for chunk in self._stream_tokens(body, chat=True):
            yield chunk

    async def completions_stream_tokens(self, body: Dict[str, Any]):
        async for chunk in self._stream_tokens(body, chat=False):
            yield chunk

    # -- fleet KV transport endpoints (ISSUE 12) --------------------------
    @staticmethod
    def _kvt():
        # lazy: the serve.llm package imports this module at load
        # time, so a top-level import back into it would be circular
        from ...serve.llm import kv_transport
        return kv_transport

    async def list_sessions(self) -> List[str]:
        """Request ids resident on this replica's engine (slots +
        waiting + parked) — the fleet migration orchestrator's view."""
        return await asyncio.get_running_loop().run_in_executor(
            None, self.engine.session_ids)

    async def export_session(self, body: Dict[str, Any]
                             ) -> Dict[str, Any]:
        """Detach one live session for shipping (drain migration /
        failover-by-restore): preempt via the PR 10 spill path,
        serialize, and terminate the local stream with a "migrated"
        finish event so the fleet relay resumes it elsewhere instead
        of reading an abort. {"session": None} when the request is
        not exportable — the caller falls back to token replay."""
        kvt = self._kvt()
        rid = str((body or {}).get("request_id") or "")
        reason = str((body or {}).get("reason") or "migration")
        state = await asyncio.get_running_loop().run_in_executor(
            None, self.engine.export_session, rid, reason)
        if state is None:
            return {"session": None}
        q = self._queues.get(rid)
        if q is not None:
            # the stream loop is blocked on its queue: deliver the
            # migration marker (req.finished is already True, so the
            # generator exits cleanly without aborting the engine)
            q.put_nowait((None, True, "migrated"))
        blob = kvt.encode_session(state)
        return {"session": kvt.to_b64(blob), "bytes": len(blob),
                "pages": int(state.get("n_pages") or 0),
                "generated": len(state.get("output_tokens") or [])}

    async def import_session(self, body: Dict[str, Any]
                             ) -> Dict[str, Any]:
        """Admit a shipped session (unary twin of
        resume_stream_tokens, for pre-staging / tests): the payload
        parks in the host tier and restores token-exact at the next
        tick. Transport/geometry faults raise — the caller treats a
        failed ship as a replay fallback, never a crash."""
        kvt = self._kvt()
        state = kvt.decode_session(
            kvt.from_b64(str((body or {}).get("session") or "")))
        kvt.ship_kind_compatible(state.get("kv_dtype"),
                                 getattr(self.engine, "_kv_kind",
                                         "f32"))
        req = await asyncio.get_running_loop().run_in_executor(
            None, self.engine.import_session, state)
        self._ensure_pump()
        self._wake.set()
        return {"request_id": req.request_id,
                "pages": int(state.get("n_pages") or 0)}

    async def prefill_export(self, body: Dict[str, Any]
                             ) -> Dict[str, Any]:
        """The disaggregated-prefill entry point: run the prompt on
        THIS replica until the first sampled token exists (prefill
        complete — the expensive long-prompt work), then park and
        export the session for a decode replica to resume. A request
        that FINISHES during prefill (1-token generations, instant
        EOS) returns the final transcript instead ("final") — there
        is nothing left to disaggregate."""
        kvt = self._kvt()
        body = dict(body or {})
        chat = bool(body.pop("_chat", False))
        rid, trace = self._trace_of(body)
        deadline = self._deadline_of(body)
        toks = self._prompt_tokens(body, chat=chat)
        self._ensure_pump()
        if not rid or rid in self._queues:
            rid = uuid.uuid4().hex[:16]
        req = Request(rid, toks, self._sampling(body),
                      lora=self._lora_for(body), trace=trace,
                      deadline=deadline,
                      priority=self._priority_of(body))
        q: asyncio.Queue = asyncio.Queue()
        self._queues[rid] = q
        try:
            # off-loop: add_request takes the step lock (racelint
            # RL002 — a mid-tick pump holds it for the whole dispatch,
            # and blocking here would stall every other stream)
            await asyncio.get_running_loop().run_in_executor(
                None, self.engine.add_request, req)
            self._wake.set()
            while not req.output_tokens and not req.finished:
                await asyncio.wait_for(q.get(), timeout=300)
            state = None
            if not req.finished:
                state = await asyncio.get_running_loop() \
                    .run_in_executor(None, self.engine.export_session,
                                     rid, "disagg")
            if state is None:
                if req.finished and req.finish_reason != "migrated":
                    # finished for real before the export could run
                    return {"session": None, "final": {
                        "i": 0, "toks": list(req.output_tokens),
                        "text": self.tokenizer.decode(
                            req.output_tokens),
                        "finished": True,
                        "reason": req.finish_reason,
                        "model": self.model_id,
                        "prompt_tokens": len(req.prompt_tokens)}}
                return {"session": None, "final": None}
            blob = kvt.encode_session(state)
            return {"session": kvt.to_b64(blob), "bytes": len(blob),
                    "pages": int(state.get("n_pages") or 0),
                    "generated": len(state.get("output_tokens")
                                     or [])}
        finally:
            self._queues.pop(rid, None)
            if not req.finished:
                self._abort_off_loop(rid)

    async def resume_stream_tokens(self, body: Dict[str, Any]):
        """Import a shipped session and stream its remaining tokens
        (the decode half of disaggregation, and the landing side of
        migration/failover-by-restore). Chunks carry GLOBAL token
        indices like *_stream_tokens; the first chunk catches the
        client up from `_resume_offset` (tokens the exporter emitted
        that never reached the client), so the fleet transcript's
        index dedup sees one gapless, exactly-once stream."""
        kvt = self._kvt()
        state = kvt.decode_session(
            kvt.from_b64(str(body.get("_session") or "")))
        kvt.ship_kind_compatible(state.get("kv_dtype"),
                                 getattr(self.engine, "_kv_kind",
                                         "f32"))
        offset = int(body.get("_resume_offset") or 0)
        self._ensure_pump()
        rid = str(state.get("request_id") or "")
        if not rid or rid in self._queues:
            rid = uuid.uuid4().hex[:16]    # see _generate: a replayed
            state["request_id"] = rid      # id must never collide
        q: asyncio.Queue = asyncio.Queue()
        self._queues[rid] = q
        req: "Request | None" = None
        try:
            req = await asyncio.get_running_loop().run_in_executor(
                None, self.engine.import_session, state)
            self._wake.set()
            out = list(req.output_tokens)
            offset = max(0, min(offset, len(out)))
            full = self.tokenizer.decode(out)
            sent = len(self.tokenizer.decode(out[:offset]))
            yield {"i": offset, "toks": out[offset:],
                   "text": full[sent:], "finished": False,
                   "reason": None, "model": self.model_id,
                   "prompt_tokens": len(req.prompt_tokens)}
            n_sent, n_toks = len(full), len(out)
            while True:
                _, finished, reason = await asyncio.wait_for(
                    q.get(), timeout=300)
                text = self.tokenizer.decode(req.output_tokens)
                delta, n_sent = text[n_sent:], len(text)
                new = list(req.output_tokens[n_toks:])
                prev = n_toks
                n_toks = len(req.output_tokens)
                if not new and not delta and not finished:
                    continue
                yield {"i": prev, "toks": new, "text": delta,
                       "finished": bool(finished),
                       "reason": reason if finished else None,
                       "model": self.model_id}
                if finished:
                    return
        finally:
            self._queues.pop(rid, None)
            if req is not None and not req.finished:
                # stream abandoned mid-resume: free the slot/pages
                self._abort_off_loop(rid)

    async def export_prefix(self, body: Dict[str, Any]
                            ) -> Dict[str, Any]:
        """Publish the cached KV pages of a prompt prefix (the fleet
        prefix store's export half). {"prefix": None} when nothing
        is cached for the chain."""
        kvt = self._kvt()
        text = str((body or {}).get("text") or "")
        if not text:
            return {"prefix": None}
        toks = self.tokenizer.encode(text)
        exp = await asyncio.get_running_loop().run_in_executor(
            None, self.engine.export_prefix, toks)
        if exp is None:
            return {"prefix": None}
        blob = kvt.encode_prefix(
            exp["tokens"], exp["k"], exp["v"],
            k_scales=exp.get("k_scales"),
            v_scales=exp.get("v_scales"),
            kv_dtype=str(exp.get("kv_dtype") or "f32"))
        return {"prefix": kvt.to_b64(blob), "bytes": len(blob),
                "tokens": len(exp["tokens"])}

    async def import_prefix(self, body: Dict[str, Any]
                            ) -> Dict[str, Any]:
        """Seed this replica's prefix cache from a published store
        entry (the import half). Returns the pages newly seeded
        (0 = already cached or no room)."""
        kvt = self._kvt()
        pfx = kvt.decode_prefix(
            kvt.from_b64(str((body or {}).get("prefix") or "")))
        kvt.ship_kind_compatible(pfx["kv_dtype"],
                                 getattr(self.engine, "_kv_kind",
                                         "f32"))
        pages = await asyncio.get_running_loop().run_in_executor(
            None, functools.partial(
                self.engine.import_prefix, pfx["tokens"], pfx["k"],
                pfx["v"], k_scales=pfx["k_scales"],
                v_scales=pfx["v_scales"], kv_dtype=pfx["kv_dtype"]))
        return {"pages": int(pages)}

    async def model_info(self) -> Dict[str, Any]:
        # stats() snapshots tick telemetry under the engine step
        # lock — run it off the event loop so a busy tick can't
        # stall other coroutines
        stats = await asyncio.get_running_loop().run_in_executor(
            None, self.engine.stats)
        return {"id": self.model_id, "object": "model",
                "owned_by": "ray_tpu",
                "adapters": sorted(self.engine._lora_raw),
                "engine": stats}

    async def register_lora(self, name: str,
                            adapters: Dict[str, Any]) -> list:
        """Live adapter registration through the deployment handle
        (off the event loop: registration serializes against step)."""
        await asyncio.get_running_loop().run_in_executor(
            None, self.engine.register_lora, name, adapters)
        return sorted(self.engine._lora_raw)

    # -- observability (ISSUE 5) -------------------------------------------
    async def metrics_text(self) -> str:
        """This replica's Prometheus text exposition (SLO histograms,
        token/finish counters, KV gauges — refreshed at scrape time).
        Off the event loop: the gauge refresh reads engine state and
        the exposition renders the whole registry."""
        return await asyncio.get_running_loop().run_in_executor(
            None, self.engine.prometheus_metrics)

    async def debug_trace(self) -> Dict[str, Any]:
        """Chrome-trace JSON of per-request lifecycle timelines."""
        return await asyncio.get_running_loop().run_in_executor(
            None, self.engine.chrome_trace)

    async def debug_events(self, since: "int | None" = None) -> Any:
        """The engine flight recorder's ring, oldest first. Without a
        cursor this is the legacy list shape; with `since` (ISSUE 20
        satellite: incremental polling) it returns only events with
        seq > since plus the ring's high-water mark, so a poller
        stops re-downloading the whole ring every scrape."""
        rec = self.engine.telemetry.recorder
        if since is None:
            return rec.events()
        return {"events": rec.events(since),
                "high_water": rec.stats()["total"]}

    async def debug_attribution(self, top_k: int = 8
                                ) -> Dict[str, Any]:
        """GET /debug/attribution (ISSUE 13): top-K cost receipts by
        FLOPs, per-tenant rollups, conservation totals. Ledger-locked
        host reads — never queues behind a tick, so no executor."""
        return self.engine.attribution_summary(int(top_k))

    async def debug_dump(self, body: "Dict[str, Any] | None" = None
                         ) -> Dict[str, Any]:
        """POST /debug/dump: snapshot a postmortem black-box bundle on
        demand (ISSUE 7). Off the event loop — the bundle renders the
        metric registry and walks host state."""
        cause = str((body or {}).get("cause") or "manual")
        bid = await asyncio.get_running_loop().run_in_executor(
            None, self.engine.dump_blackbox, cause)
        return {"replica": self.replica_id, "bundle": bid,
                "spool_dir": self.engine.blackbox.root}

    async def debug_bundles(self) -> List[Dict[str, Any]]:
        """Black-box spool listing (id, cause, ts, bytes) — oldest
        first; served merged at GET /fleet/debug/bundles. A bundle the
        engine's writer thread is still gathering (a tick anomaly's) is
        waited for, briefly and off the event loop."""
        await asyncio.get_running_loop().run_in_executor(
            None, self.engine.wait_for_profile, 5.0)
        return self.engine.blackbox.list()

    async def debug_bundle(self, bundle_id: str
                           ) -> "Dict[str, Any] | None":
        """Fetch one postmortem bundle by id (None when unknown)."""
        return await asyncio.get_running_loop().run_in_executor(
            None, self.engine.blackbox.read, str(bundle_id))

    async def start_profile(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """Arm jax.profiler capture of the next N engine ticks
        (POST /debug/profile). Serializes against step() via the
        engine's step lock — run off the event loop."""
        body = body or {}
        # default only when the key is absent/null — an explicit
        # {"ticks": 0} must reach the engine and be rejected there,
        # not silently arm the 8-tick default
        ticks = body.get("ticks")
        ticks = 8 if ticks is None else int(ticks)
        log_dir = body.get("log_dir")
        out = await asyncio.get_running_loop().run_in_executor(
            None, self.engine.profile_next_ticks, ticks, log_dir)
        return {"model": self.model_id, "log_dir": out, "ticks": ticks}

    # -- fleet surface (ISSUE 6) -------------------------------------------
    def _fleet_stats_sync(self) -> Dict[str, Any]:
        """Routing inputs for the fleet router. Plain host-side
        attribute reads (no step-lock, no device sync) — the router
        refreshes this at sub-second cadence and must never queue
        behind a tick. The step-lock-guarded counters (active/waiting/
        lanes/parked/preemptions/page-pressure) come from the engine's
        PUBLISHED immutable snapshot (fleet_counters(), rebuilt under
        the lock by every mutating entry point) instead of walking the
        live waiting list / slot table — the pre-racelint version
        summed over `eng.waiting` while the pump rebinds it, which
        could glitch the autoscaler's overload signals."""
        eng = self.engine
        alloc = eng.allocator
        used = alloc.used_pages
        last = eng.last_step_at
        counters = eng.fleet_counters()
        lanes = counters["lanes"]
        return {
            "replica": self.replica_id,
            "model": self.model_id,
            # slice topology (ISSUE 17): chips this replica's engine
            # mesh occupies — the fleet's slice-accounting unit
            # (ReplicaSnapshot.chips, /fleet rows, autoscaler sizing)
            "chips": getattr(eng, "n_chips", 1),
            "active": counters["active"],
            "waiting": counters["waiting"],
            "kv_occupancy": (used / alloc.num_usable
                             if alloc.num_usable else 0.0),
            "free_pages": alloc.free_pages,
            "cache_hit_rate": alloc.cache_hit_rate,
            # monotonic difference: an NTP step must not fake a wedged
            # (or freshly-ticked) replica to the router
            "last_tick_age_s": (None if last is None
                                else max(time.monotonic() - last, 0.0)),
            # KV memory hierarchy (ISSUE 10): the autoscaler/watchdog's
            # page-pressure signal + host-tier occupancy for /fleet
            "page_pressure": counters["page_pressure"],
            # batch lane (ISSUE 14): the serving plane subtracts the
            # preemptible tier from its overload signals
            **lanes,
            "kv_occupancy_batch": (
                lanes["batch_kv_pages"] / alloc.num_usable
                if alloc.num_usable else 0.0),
            "parked_sessions": counters["parked_sessions"],
            "kv_offload": eng.host_tier is not None,
            "kv_host_pages_used": (eng.host_tier.used_pages
                                   if eng.host_tier else 0),
            # ISSUE 12 satellite: host-tier BYTE occupancy — byte
            # pressure from migration/prefix-store traffic surfaces
            # before page counts saturate
            "kv_host_bytes_used": (eng.host_tier.used_bytes
                                   if eng.host_tier else 0),
            "spills_total": (eng.host_tier.spills_total
                             if eng.host_tier else 0),
            "restores_total": (eng.host_tier.restores_total
                               if eng.host_tier else 0),
            "preemptions_total": counters["preemptions_total"],
            # per-dispatch perf accounting (ISSUE 11): the fleet-plane
            # brief — MFU/MBU/roofline + phase goodput — so /fleet
            # rows and the fleet gauges see utilization per replica
            "perf": (eng.perf.brief() if eng.perf is not None
                     else None),
            # tick-anomaly analyzer (ISSUE 13): the recent anomaly
            # rate + totals ride every snapshot so /fleet rows show
            # them and the fleet watchdog reads the rate as a page
            # precursor
            "anomaly": (None if eng.anomaly is None else {
                "rate": eng.anomaly.rate(),
                "total": eng.anomaly.anomalies_total,
                "last_kind": ((eng.anomaly.last or {}).get("kind")
                              if eng.anomaly.last else None),
            }),
            # cumulative SLO sums the fleet autoscaler deltas into
            # recent-window TTFT / queue-wait means
            "slo_totals": eng.telemetry.slo_totals(),
        }

    async def fleet_stats(self) -> Dict[str, Any]:
        return self._fleet_stats_sync()

    async def health_detail(self) -> Dict[str, Any]:
        """Per-replica health row surfaced through serve.status()
        (the controller's metrics poll calls this): the router's
        inputs — queue depth, KV occupancy, last-tick age — without
        operators having to hit each replica's /stats."""
        out = self._fleet_stats_sync()
        out.pop("slo_totals", None)
        return out

    async def drain(self, timeout_s: float = 30.0) -> Dict[str, Any]:
        """Run the engine dry WITHOUT dropping in-flight work: the
        fleet removed this replica from its router ring first, so no
        new requests arrive; existing requests keep streaming through
        the pump until each finishes naturally (has_work() also counts
        pipelined in-flight ticks and pending folds, so a clean return
        means every lagged token has been delivered). Scale-down calls
        this before parking the replica on standby."""
        t0 = time.monotonic()
        while self.engine.has_work() \
                and time.monotonic() - t0 < timeout_s:
            if self._wake is not None:
                self._wake.set()     # keep the pump ticking
            await asyncio.sleep(0.01)
        return {"replica": self.replica_id,
                "drained": not self.engine.has_work(),
                "waited_s": round(time.monotonic() - t0, 3)}

    async def check_health(self) -> None:
        return None


class LLMRouterImpl:
    """OpenAI-route ingress; fans out to per-model LLMServer handles."""

    def __init__(self, *server_handles):
        self._servers: Dict[str, Any] = {}
        self._handles = list(server_handles)
        self._resolved = False

    async def _resolve(self) -> None:
        if not self._resolved:
            for h in self._handles:
                info = await h.model_info.remote()
                self._servers[info["id"]] = h
                # adapter names route to their base model's server
                # (vLLM convention: model=<adapter> selects base+LoRA)
                for adapter in info.get("adapters") or []:
                    self._servers.setdefault(adapter, h)
            self._resolved = True

    def _pick(self, body: Dict[str, Any]):
        model = body.get("model")
        if model and model in self._servers:
            return self._servers[model]
        if model and model not in self._servers:
            return None
        return next(iter(self._servers.values()))

    def _unique_servers(self) -> List[tuple]:
        """(model_id, handle) per distinct server. Adapter names alias
        their base model's handle; _resolve inserts each handle under
        its model_id FIRST, so the first key seen per handle is the
        model id."""
        out: List[tuple] = []
        for mid, h in self._servers.items():
            if any(h is s for _, s in out):
                continue
            out.append((mid, h))
        return out

    async def _handle_get(self, norm: str,
                          query: "Dict[str, Any] | None" = None
                          ) -> Any:
        """Every GET endpoint, dispatched BEFORE any body parse — an
        unknown GET path is a clean 404 instead of the confusing
        'invalid JSON body' 400 the old fallthrough produced."""
        from ...serve import Response

        query = query or {}

        if norm == "/v1/models":
            models = [{"id": mid, "object": "model", "owned_by": "ray_tpu"}
                      for mid in self._servers]
            return {"object": "list", "data": models}
        if norm == "/stats":
            # serving observability (ISSUE 4/5): per-model engine
            # stats — tick_times (pipelined-tick overlap) plus the
            # request-lifecycle SLO summary ("requests": TTFT/ITL/
            # queue-wait aggregates, finish-reason counts).
            stats: Dict[str, Any] = {}
            for _, h in self._unique_servers():
                info = await h.model_info.remote()
                stats[info["id"]] = info["engine"]
            return {"object": "stats", "models": stats}
        if norm == "/metrics":
            # Prometheus text exposition (ISSUE 5): every replica
            # renders its own process registry (samples tagged per
            # model), then the blocks MERGE — in-process replicas
            # share one registry, so naive concatenation would repeat
            # every series once per replica and Prometheus rejects
            # the scrape; merging collapses duplicate samples and
            # keeps one # HELP/# TYPE header per family.
            from ...util.metrics import merge_expositions
            texts = []
            for _, h in self._unique_servers():
                texts.append(await h.metrics_text.remote())
            return Response(merge_expositions(texts), status=200,
                            content_type="text/plain")
        if norm == "/debug/trace":
            # Chrome-trace JSON (chrome://tracing, Perfetto): one tid
            # per request with queued/prefill/decode lifecycle spans;
            # metadata carries each engine's tracing-ring fill/drop
            # counters so a truncated ring reads as truncated
            events: List[Any] = []
            meta: Dict[str, Any] = {}
            for mid, h in self._unique_servers():
                doc = await h.debug_trace.remote()
                events.extend(doc.get("traceEvents") or [])
                if doc.get("metadata"):
                    meta[mid] = doc["metadata"]
            return {"traceEvents": events, "displayTimeUnit": "ms",
                    "metadata": meta}
        if norm == "/debug/events":
            # engine flight recorders (bounded structured-event
            # rings); ?since=<seq> polls incrementally (ISSUE 20
            # satellite): each model returns only events newer than
            # the cursor plus its ring's high-water mark
            since = parse_since(query.get("since"))
            out: Dict[str, Any] = {}
            for mid, h in self._unique_servers():
                out[mid] = await h.debug_events.remote(since)
            return {"object": "events", "models": out}
        if norm == "/debug/attribution":
            # per-request cost receipts + tenant rollups (ISSUE 13)
            out = {}
            for mid, h in self._unique_servers():
                out[mid] = await h.debug_attribution.remote()
            return {"object": "attribution", "models": out}
        return Response({"error": f"no route {norm}"}, status=404,
                        content_type="application/json")

    async def _handle_profile(self, body: Dict[str, Any]) -> Any:
        """POST /debug/profile: arm a capture of the next N engine
        ticks under jax.profiler ({"ticks": N, "model": optional
        target, "log_dir": optional}). Responds per model with the
        log dir (or the arming error, e.g. a capture already
        pending)."""
        from ...serve import Response

        target = body.get("model")
        out: Dict[str, Any] = {}
        for mid, h in self._unique_servers():
            if target and mid != target:
                continue
            try:
                out[mid] = await h.start_profile.remote(body)
            except Exception as e:
                out[mid] = {"error": repr(e)}
        if not out:
            return Response(
                {"error": f"model {target!r} not found"},
                status=404, content_type="application/json")
        return {"object": "profile", "models": out}

    async def __call__(self, request) -> Any:
        from ...serve import Response

        await self._resolve()
        path = getattr(request, "path", "/")
        method = getattr(request, "method", "POST")
        norm = path.rstrip("/") or "/"
        if method == "GET":
            return await self._handle_get(
                norm, dict(getattr(request, "query_params", None)
                           or {}))
        try:
            body = request.json()
        except Exception:
            return Response({"error": "invalid JSON body"}, status=400,
                            content_type="application/json")
        if isinstance(body, dict):
            # plumbing keys are INTERNAL (the fleet ingress mints
            # them): a client forging `_request_id`/`_trace` through
            # this standalone ingress could replay a finished
            # request's id or stitch its spans into another trace's
            # forensics, and `_continue_tokens`/`_token_offset`/
            # `_deadline_epoch` are the failover continuation's
            # plumbing (ISSUE 9) — strip them all at the door
            # (clients express deadlines via `deadline_s`)
            for k in INTERNAL_BODY_KEYS:
                body.pop(k, None)
        if norm == "/debug/profile":
            return await self._handle_profile(
                body if isinstance(body, dict) else {})
        if norm == "/debug/dump":
            # POST /debug/dump: black-box every model's engine now
            out = {}
            for mid, h in self._unique_servers():
                out[mid] = await h.debug_dump.remote(
                    body if isinstance(body, dict) else {})
            return {"object": "dump", "models": out}
        server = self._pick(body)
        if server is None:
            # a LoRA adapter may have been registered after the first
            # resolve: refresh the model map once before 404ing
            self._resolved = False
            await self._resolve()
            server = self._pick(body)
        if server is None:
            return Response(
                {"error": f"model {body.get('model')!r} not found"},
                status=404, content_type="application/json")
        streaming = bool(body.get("stream"))
        if path.rstrip("/").endswith("/chat/completions"):
            if streaming:
                from ...serve import StreamingHint
                return StreamingHint("stream_chat", body)
            return await server.chat.remote(body)
        if path.rstrip("/").endswith("/completions"):
            if streaming:
                from ...serve import StreamingHint
                return StreamingHint("stream_completions", body)
            return await server.completions.remote(body)
        return Response({"error": f"no route {path}"}, status=404,
                        content_type="application/json")

    async def stream_chat(self, body: Dict[str, Any]):
        """Proxy-invoked SSE relay: streams from the model server
        deployment through this ingress to the HTTP client."""
        await self._resolve()
        server = self._pick(body)
        gen = server.chat_stream.options(stream=True).remote(body)
        async for chunk in gen:
            yield chunk

    async def stream_completions(self, body: Dict[str, Any]):
        await self._resolve()
        server = self._pick(body)
        gen = server.completions_stream.options(stream=True).remote(body)
        async for chunk in gen:
            yield chunk
