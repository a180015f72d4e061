"""Serving drivers: fixed-batch (static) and continuous-batching decode over a
request queue, with the KV cache living on-device across steps.

Two schedulers share the model's prefill/decode executables:

``serve_static`` — PR 1's fixed-group path, kept as the regression baseline:
requests are grouped into fixed-size batches, each group prefills together and
decodes until every slot has hit its own EOS or budget (per-slot ``done``
tracking stops a group early; finished and padding slots no longer drag the
loop to the group-wide max).

``ContinuousEngine`` / ``serve_continuous`` — an admission queue with
mid-stream slot refill: every batch slot carries its own request state
(budget, EOS id, RNG stream, absolute position clock).  When a slot finishes,
the host prefills the next queued request alone (one one-row prefill per
admitted request, written into its slot of the live cache in place) — the
same two jitted executables (``prefill``, ``decode_step``) serve the whole
queue, with zero recompiles across refills (a traced slot index keeps every
prefill at one shape, per-slot positions every decode tick).

Result accounting is per-request: ``Result.tokens`` is truncated at the
request's own first EOS (inclusive) and ``Result.steps`` counts the tokens
actually generated for that request; the batch-wide round count lives on the
engine (``engine.batch_steps``) together with the wasted-slot-step counters
the serve benchmark gates on.
"""

from __future__ import annotations

import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.obs.trace import MAIN_TID, SLOT_TID0


@dataclass
class Request:
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 32
    eos_id: int = -1  # -1: never stops early


@dataclass
class Result:
    tokens: np.ndarray  # truncated at this request's first EOS (inclusive)
    prompt_len: int
    steps: int  # tokens generated for THIS request (== len(tokens))


def _trim_at_eos(tokens: np.ndarray, budget: int, eos_id: int) -> np.ndarray:
    """This request's tokens: at most ``budget``, cut at the first EOS
    (keeping the EOS token itself)."""
    tokens = tokens[:budget]
    if eos_id >= 0:
        hits = np.flatnonzero(tokens == eos_id)
        if hits.size:
            tokens = tokens[: hits[0] + 1]
    return tokens


def _jit_cache_size(fn) -> int:
    """Number of compiled specializations behind a jax.jit wrapper (-1 if the
    runtime doesn't expose it)."""
    try:
        return fn._cache_size()
    except Exception:
        return -1


class _EngineBase:
    """Shared engine plumbing: compiled-executable bookkeeping and the
    batch-round / wasted-slot-step counters both schedulers report.

    A slot-round is one slot position in one sampling round (prefill round or
    decode step); it counts as wasted when it yields no token for a live
    request.  Caveat the serve bench documents: the static prefill round
    counts every slot as useful (a padding dummy's first token is kept by its
    1-token budget even though the caller never sees it), which makes the
    static number look BETTER, so the continuous-vs-static gate is
    conservative.  The continuous engine prefills one row per call, so it
    counts each as ``1/B`` of a round, with no slot wasted."""

    def reset_counters(self) -> None:
        self.batch_steps = 0  # sampling rounds of B rows (prefill + decode)
        self.wasted_slot_steps = 0
        self.compile_time_s = 0.0  # wall time inside compile-flagged spans
        metrics = getattr(self, "metrics", None)
        if metrics is not None:
            metrics.reset()

    @property
    def wasted_fraction(self) -> float:
        total = self.B * self.batch_steps
        return self.wasted_slot_steps / total if total else 0.0

    def compile_counts(self) -> dict:
        return {name: _jit_cache_size(fn)
                for name, fn in self._executables.items()}


# The ContinuousEngine's phase spans: name -> the span it nests in.  An
# admission round and a no-feedback decode span each split into the host's
# own work and the two places it waits on the device (WAIT_SPANS).
ENGINE_SPANS = {
    "engine.serve": None,  # one serve() call
    "engine.admit": "engine.serve",  # one admission round
    "engine.prefill": "engine.admit",  # per admitted row: build, H2D, dispatch
    "engine.prefill_wait": "engine.admit",  # host waits on prefill logits
    "engine.first_tokens": "engine.admit",  # sampling + first emits
    "engine.decode": "engine.serve",  # one span of k fused ticks
    "engine.dispatch": "engine.decode",  # the k tick dispatches
    "engine.settle": "engine.decode",  # host waits on the k tokens
    "engine.emit": "engine.decode",  # per-token bookkeeping + callbacks
}
WAIT_SPANS = ("engine.prefill_wait", "engine.settle")


@contextmanager
def _phase_span(engine, tracer, name: str, cat: str = "serve", fn=None,
                **args):
    """One engine phase, written to two sinks and never synced: the span
    ends when the host leaves the phase, so a phase that dispatches ends at
    dispatch, and time the host spends waiting on the device shows in the
    phase that waits.

    Always a ``jax.profiler.TraceAnnotation`` (``args`` as its stats): the
    profiler's host plane, on the device trace's clock, obs on or off.  With
    a ScopeKit ``tracer`` (the caller passes one only when
    ``obs.enabled()``) also B/E on its engine track; the body may then set
    ``end_args`` (extra fields for the E event) on the yielded dict.  If
    ``fn``'s jit cache grew during the span, the E event is flagged
    ``compiled=True``, a ``jit.compile`` instant is emitted, and the duration
    feeds ``engine.compile_time_s`` -- the number the CLIs subtract to report
    steady-state throughput.  After the span, ``st["dur_s"]`` holds its
    duration."""
    with jax.profiler.TraceAnnotation(name, **args):
        if tracer is None:
            yield {}
            return
        before = _jit_cache_size(fn) if fn is not None else -1
        tracer.begin(name, cat, **args)
        t0 = time.perf_counter()
        st: dict = {}
        try:
            yield st
        finally:
            st["dur_s"] = time.perf_counter() - t0
            end_args = dict(st.get("end_args") or {})
            if fn is not None and _jit_cache_size(fn) > before:
                end_args["compiled"] = True
                engine.compile_time_s += st["dur_s"]
                tracer.instant("jit.compile", "jit", phase=name)
            tracer.end(name, cat, **end_args)


def _place_engine_packs(model, mesh) -> None:
    """Pre-place the model's sharded approx pack before jitting the engine
    executables (``ApproxConfig.place_packs``): idempotent when
    ``build_model(cfg, mesh=...)`` already placed it, and covers engines whose
    mesh only exists at serve time — packs requested after this call capture
    per-core slices instead of paying a first-dispatch reshard."""
    if mesh is None:
        from repro.parallel.sharding import current_mesh
        mesh = current_mesh()
    approx = getattr(getattr(model, "cfg", None), "approx", None)
    if approx is not None:
        approx.place_packs(mesh)


def _check_engine_batch(engine, batch_size: int) -> None:
    if engine.B != batch_size:
        raise ValueError(f"engine batch size {engine.B} != requested "
                         f"{batch_size} (a passed engine overrides cache_len/"
                         "temperature/seed; batch_size must agree)")


class DecodeEngine(_EngineBase):
    """Fixed-batch prefill + decode (the static scheduler's inner engine)."""

    def __init__(self, model, params, batch_size: int, cache_len: int,
                 temperature: float = 0.0, seed: int = 0, mesh=None):
        self.model = model
        self.params = params
        self.B = batch_size
        self.cache_len = cache_len
        self.temperature = temperature
        self.key = jax.random.key(seed)
        self.metrics = obs.Registry()  # ttft_s / itl_s histograms
        _place_engine_packs(model, mesh)
        self._prefill = jax.jit(model.prefill)
        self._step = jax.jit(model.decode_step)
        self._executables = {"prefill": self._prefill,
                             "decode_step": self._step}
        self.reset_counters()

    def _sample(self, logits: jax.Array) -> jax.Array:
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        self.key, sub = jax.random.split(self.key)
        return jax.random.categorical(sub, logits / self.temperature, axis=-1)

    def generate_batch(self, prompts: np.ndarray, max_new,
                       eos_id=-1, extra_inputs: Optional[dict] = None):
        """prompts: (B, S) int32, right-aligned equal length (caller pads).

        ``max_new`` and ``eos_id`` are scalars applied to the whole batch or
        (B,) vectors of per-slot budgets / EOS ids (-1: that slot never stops
        early).  Returns ``(tokens, steps)`` where ``steps`` is the
        batch-wide sampling-round count (every round samples one token per
        slot, including the round fed by the prefill logits); the loop stops
        as soon as EVERY slot has hit its own EOS or its own budget, so
        finished and padding slots never drag the group to the max budget.
        """
        B, S = prompts.shape
        assert B == self.B
        rec = obs.enabled()
        tracer = obs.get_tracer() if rec else None
        t0 = time.perf_counter()
        eos = np.broadcast_to(np.asarray(eos_id, np.int64), (B,))
        budget = np.broadcast_to(np.asarray(max_new, np.int64), (B,))
        horizon = int(budget.max())
        cache = self.model.init_cache(B, self.cache_len)
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if extra_inputs:
            batch.update({k: jnp.asarray(v) for k, v in extra_inputs.items()})
        with _phase_span(self, tracer, "static.prefill", fn=self._prefill,
                         batch=B, prompt_len=S):
            logits, cache = self._prefill(self.params, batch, cache)
            if rec:  # the span then measures work, not dispatch
                jax.block_until_ready(logits)
        out = [self._sample(logits)]
        self.batch_steps += 1
        if rec:
            np.asarray(out[0])  # settle the first tokens for an honest TTFT
            self.metrics.histogram("ttft_s").observe(time.perf_counter() - t0)
        # only force a device->host sync per step when some slot can stop early
        has_eos = bool((eos >= 0).any())
        done = budget <= 1
        if has_eos:
            done = done | ((eos >= 0) & (np.asarray(out[0]) == eos))
        steps = 1  # the prefill logits already yielded one token
        with _phase_span(self, tracer, "static.decode", fn=self._step) as st:
            for i in range(horizon - 1):
                if done.all():
                    break
                self.wasted_slot_steps += int(done.sum())
                tok = out[-1][:, None].astype(jnp.int32)
                logits, cache = self._step(self.params, tok,
                                           jnp.asarray(S + i, jnp.int32),
                                           cache)
                nxt = self._sample(logits)
                out.append(nxt)
                steps += 1
                self.batch_steps += 1
                done = done | (budget <= steps)
                if has_eos:
                    done = done | ((eos >= 0) & (np.asarray(nxt) == eos))
            if rec:
                jax.block_until_ready(out[-1])
                st["end_args"] = {"steps": steps - 1}
        if rec and steps > 1:
            # decode ticks are uniform in the static loop, so the amortized
            # per-step interval stands in for each inter-token latency
            itl = st["dur_s"] / (steps - 1)
            hist = self.metrics.histogram("itl_s")
            for _ in range(steps - 1):
                hist.observe(itl)
        return np.stack([np.asarray(t) for t in out], axis=1), steps


def pad_and_batch(requests: List[Request], batch_size: int, pad_id: int = 0):
    """Left-pad prompts to a common length; group into fixed-size batches."""
    groups = [requests[i : i + batch_size]
              for i in range(0, len(requests), batch_size)]
    out = []
    for g in groups:
        while len(g) < batch_size:
            g = g + [Request(prompt=np.zeros((1,), np.int32), max_new_tokens=1)]
        maxlen = max(len(r.prompt) for r in g)
        toks = np.full((batch_size, maxlen), pad_id, np.int32)
        for i, r in enumerate(g):
            toks[i, maxlen - len(r.prompt):] = r.prompt
        out.append((g, toks))
    return out


def serve_static(model, params, requests: List[Request], batch_size: int,
                 cache_len: int, temperature: float = 0.0, seed: int = 0,
                 engine: Optional[DecodeEngine] = None) -> List[Result]:
    """Fixed-group scheduler: one prefill + decode loop per group of
    ``batch_size`` requests (short groups padded with 1-token dummies).
    Pass ``engine`` to reuse compiled executables across calls and to read
    the round/wasted-step counters afterwards — the engine's own cache_len/
    temperature/seed then apply and those arguments are ignored."""
    if engine is None:
        engine = DecodeEngine(model, params, batch_size, cache_len, temperature,
                              seed)
    else:
        _check_engine_batch(engine, batch_size)
    results: List[Result] = []
    for group, toks in pad_and_batch(requests, batch_size):
        budgets = np.asarray([r.max_new_tokens for r in group], np.int64)
        eos = np.asarray([r.eos_id for r in group], np.int64)
        gen, _ = engine.generate_batch(toks, budgets, eos)
        for i, r in enumerate(group):
            kept = _trim_at_eos(gen[i], r.max_new_tokens, r.eos_id)
            results.append(Result(tokens=kept, prompt_len=len(r.prompt),
                                  steps=len(kept)))
    return results[: len(requests)]


# Legacy name: PR 1..3 callers imported ``serve`` for the fixed-batch path.
serve = serve_static


# ======================================================================================
# Continuous batching: admission queue + mid-stream slot refill
# ======================================================================================


def cache_batch_axes(model, cache_len: int):
    """Per-leaf batch axis of the model's decode cache, inferred by comparing
    abstract caches at two batch sizes.  Every leaf must carry exactly one
    batch axis — per-slot position buffers included — or slot refill cannot
    gather/scatter that leaf."""
    a = jax.eval_shape(lambda: model.init_cache(1, cache_len))
    b = jax.eval_shape(lambda: model.init_cache(2, cache_len))

    def one(x, y):
        diffs = [i for i, (p, q) in enumerate(zip(x.shape, y.shape)) if p != q]
        if len(diffs) != 1:
            raise ValueError(
                "cache leaf without a unique batch axis: "
                f"{x.shape} vs {y.shape} — ContinuousEngine needs per-slot "
                "cache rows (shared-clock caches cannot be refilled)")
        return diffs[0]

    return jax.tree.map(one, a, b)


def scatter_cache_slots(dst, src, slot_ids: Sequence[int], axes):
    """dst[..., slot, ...] = src[..., slot, ...] for each listed slot, per
    leaf along its batch axis, as eager ops.  The serve loop does not use it
    (its prefill writes the admitted slot in place)."""
    sl = jnp.asarray(list(slot_ids), jnp.int32)

    def one(d, s, ax):
        idx = (slice(None),) * ax + (sl,)
        return d.at[idx].set(jnp.take(s, sl, axis=ax))

    return jax.tree.map(one, dst, src, axes)


@dataclass
class _Slot:
    req_idx: int
    prompt_len: int
    budget: int
    eos_id: int
    emitted: list = field(default_factory=list)


class ContinuousEngine(_EngineBase):
    """Admission queue + per-slot lifecycle + mid-stream slot refill.

    Every prompt is left-padded to one fixed prefill width (``prefill_len``,
    default: the queue's longest prompt).  Admission — initial fill and every
    refill — prefills each admitted request alone: one call of ONE compiled
    prefill per request, which computes that one row and writes it into its
    slot of the live cache in place (the slot index is traced, so every call
    has the same shapes); per-slot position clocks keep every decode tick at
    one shape, so the whole queue is served by exactly two executables
    (assert via ``compile_counts()``).  Greedy output is token-identical to
    serving each request alone (per-request oracle): slot rows never
    interact, and a refilled slot's cache rows are exactly the rows a solo
    prefill produces.

    Token-only prompts (models whose prefill needs extra inputs — encoder
    frames, vision patches — are served by ``serve_static`` only).
    """

    def __init__(self, model, params, batch_size: int, cache_len: int,
                 temperature: float = 0.0, seed: int = 0,
                 prefill_len: Optional[int] = None, pad_id: int = 0,
                 mesh=None):
        self.model = model
        self.params = params
        self.B = batch_size
        self.cache_len = cache_len
        self.temperature = temperature
        self.key = jax.random.key(seed)
        self.prefill_len = prefill_len
        self.pad_id = pad_id
        self.metrics = obs.Registry()  # ttft_s / itl_s / queue_wait_s
        _place_engine_packs(model, mesh)
        self._axes = axes = cache_batch_axes(model, cache_len)

        # One admitted request per call: row ``slot`` of ``tokens`` (the other
        # rows are pad) is prefilled alone against a one-row cache, which then
        # overwrites row ``slot`` of every leaf of the live cache, position
        # rows included.  The live cache is donated, so it is updated in
        # place; the logits are (B, V) with the admitted row at ``slot`` and
        # zeros elsewhere.
        def prefill(params, batch, cache):
            slot = batch["slot"]
            row = jax.lax.dynamic_slice_in_dim(batch["tokens"], slot, 1)
            logits, rcache = model.prefill(params, {"tokens": row},
                                           model.init_cache(1, cache_len))
            cache = jax.tree.map(
                lambda d, s, ax: jax.lax.dynamic_update_slice_in_dim(
                    d, s.astype(d.dtype), slot, ax), cache, rcache, axes)
            out = jnp.zeros((batch_size,) + logits.shape[1:], logits.dtype)
            return jax.lax.dynamic_update_slice_in_dim(out, logits, slot,
                                                       0), cache

        self._prefill = jax.jit(prefill, donate_argnums=2)

        # One fused executable per decode tick: step + greedy argmax + clock
        # advance, with the fed-back token and the per-slot positions staying
        # device-resident (host pushes them only at refill rounds — per-tick
        # host->device transfers would otherwise rival the step itself).
        def tick(params, tok, pos, cache):
            logits, cache = model.decode_step(params, tok, pos, cache)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt[:, None], logits, pos + 1, cache

        self._tick = jax.jit(tick)
        self._executables = {"prefill": self._prefill,
                             "decode_step": self._tick}
        self._fresh = None  # the empty B-row cache every queue starts from
        self.reset_counters()

    def reset_counters(self) -> None:
        super().reset_counters()
        self.prefills = 0  # prefill work in (B, S0) prefills: rows / B
        self.prefill_rows = 0  # rows prefilled: one per admitted request
        self.refills = 0  # admissions into a previously-used slot

    # ------------------------------ sampling ---------------------------------

    def _sample_row(self, row: np.ndarray, req_idx: int, tok_step: int) -> int:
        """Per-request RNG stream: token ``tok_step`` of request ``req_idx``
        depends only on (engine seed, req_idx, tok_step, that row's logits) —
        reproducible regardless of which slot the request landed in."""
        if self.temperature <= 0.0:
            return int(np.argmax(row))
        k = jax.random.fold_in(jax.random.fold_in(self.key, req_idx), tok_step)
        return int(jax.random.categorical(
            k, jnp.asarray(row) / self.temperature))

    # ------------------------------- serve -----------------------------------

    def serve(self, requests: List[Request],
              on_result: Optional[Callable[[int, Result], None]] = None,
              on_token: Optional[Callable[[int, int, float], None]] = None
              ) -> List[Result]:
        """Serve the whole queue; returns one ``Result`` per request.

        ``on_result(req_idx, result)`` fires when a request finishes.
        ``on_token(req_idx, n, t)`` fires once per served token, in order
        (``n == 0`` at a request's first token), with ``t`` the
        ``time.perf_counter()`` at which token ``n`` settled on the host --
        the streaming server's hook, costing one ``if`` when ``None``."""
        if not requests:
            return []
        B = self.B
        S0 = self.prefill_len or max(len(r.prompt) for r in requests)
        longest = max(len(r.prompt) for r in requests)
        if longest > S0:
            raise ValueError(f"prompt of length {longest} exceeds the "
                             f"prefill width {S0}")
        if S0 > self.cache_len:
            raise ValueError(f"prefill width {S0} exceeds cache_len "
                             f"{self.cache_len}")
        if self._fresh is None:
            self._fresh = self.model.init_cache(B, self.cache_len)
        tracer = obs.get_tracer() if obs.enabled() else None
        if tracer is not None:
            tracer.set_thread_name(MAIN_TID, "engine")
        with _phase_span(self, tracer, "engine.serve", requests=len(requests),
                         batch=B, prefill_len=S0):
            return self._serve_queue(requests, S0, tracer, on_result,
                                     on_token)

    def _serve_queue(self, requests, S0, tracer, on_result, on_token):
        # Per-request lifecycle spans live on one trace track per slot
        # (tid = SLOT_TID0 + slot): a slot serves one request at a time, so
        # every track's B/E events are balanced and non-overlapping.  All
        # requests enqueue at serve() entry, so queue_wait_s is admission
        # time minus t0 and ttft_s additionally includes the prefill.
        B = self.B
        rec = tracer is not None
        t0 = time.perf_counter()
        results: List[Optional[Result]] = [None] * len(requests)
        pending = deque(enumerate(requests))
        live: List[Optional[_Slot]] = [None] * B
        used = [False] * B  # slots occupied before (this call): refill marker
        # the prefill donates the live cache: start from a copy of the empty
        # one, which stays for the next queue
        cache = jax.tree.map(jnp.copy, self._fresh)
        pos = np.zeros((B,), np.int64)  # host mirror of the per-slot clocks
        last = np.zeros((B,), np.int64)  # host mirror of last sampled tokens
        tok_dev = None  # (B, 1) int32 device-resident fed-back token
        pos_dev = None  # (B,) int32 device-resident clocks

        def emit(j: int, tok: int, t: float) -> None:
            s = live[j]
            if on_token is not None:
                on_token(s.req_idx, len(s.emitted), t)
            s.emitted.append(tok)
            if (s.eos_id >= 0 and tok == s.eos_id) or \
                    len(s.emitted) >= s.budget:
                res = Result(tokens=np.asarray(s.emitted, np.int64),
                             prompt_len=s.prompt_len, steps=len(s.emitted))
                results[s.req_idx] = res
                if on_result is not None:
                    on_result(s.req_idx, res)
                if rec:
                    tracer.end("request", "request", SLOT_TID0 + j,
                               tokens=len(s.emitted))
                live[j] = None

        def take_free():
            """The next admission's (slot, req_idx, request) triples, one per
            free slot while the queue lasts; zero-budget requests finish on
            the way."""
            take = []
            for j in [j for j in range(B) if live[j] is None]:
                i, r = None, None
                while pending:  # zero-budget requests never take a slot
                    i, r = pending.popleft()
                    if r.max_new_tokens >= 1:
                        break
                    res = Result(tokens=np.zeros((0,), np.int64),
                                 prompt_len=len(r.prompt), steps=0)
                    results[i] = res
                    if on_result is not None:
                        on_result(i, res)
                    i, r = None, None
                if r is None:
                    break
                take.append((j, i, r))
            return take

        while True:
            # admission: one one-row prefill per free slot, back to back,
            # each into its slot of the cache the previous call returned;
            # one wait for all their logits (budget-1 / instant-EOS
            # admissions free their slot immediately, so keep refilling
            # until slots or queue run dry)
            admitted = False
            while pending and any(s is None for s in live):
                with _phase_span(self, tracer, "engine.admit",
                                 fn=self._prefill) as adm:
                    with _phase_span(self, tracer, "engine.prefill"):
                        take = take_free()
                        t_admit = time.perf_counter()
                        outs = []
                        for j, _, r in take:
                            # only row j holds a prompt, so each call's
                            # block names the one request it admits
                            rows = np.full((B, S0), self.pad_id, np.int32)
                            rows[j, S0 - len(r.prompt):] = r.prompt
                            logits, cache = self._prefill(
                                self.params,
                                {"tokens": jnp.asarray(rows),
                                 "slot": jnp.asarray(j, jnp.int32)}, cache)
                            outs.append(logits)
                    if not take:
                        break
                    # each call computes and samples its one admitted row:
                    # 1/B of a batch round, with no slot wasted
                    self.prefill_rows += len(take)
                    self.prefills += len(take) / B
                    self.batch_steps += len(take) / B
                    self.refills += sum(used[j] for j, _, _ in take)
                    if rec:
                        adm["end_args"] = {"admitted": len(take),
                                           "prefill_rows": self.prefill_rows}
                    for j, _, _ in take:
                        used[j] = True
                    with _phase_span(self, tracer, "engine.prefill_wait"):
                        lgs = jax.device_get(outs)
                    t_first = time.perf_counter()
                    with _phase_span(self, tracer, "engine.first_tokens"):
                        for (j, i, r), lg in zip(take, lgs):
                            live[j] = _Slot(req_idx=i,
                                            prompt_len=len(r.prompt),
                                            budget=r.max_new_tokens,
                                            eos_id=r.eos_id)
                            pos[j] = S0
                            if rec:
                                tracer.set_thread_name(SLOT_TID0 + j,
                                                       f"slot {j}")
                                tracer.begin("request", "request",
                                             SLOT_TID0 + j, req_idx=i,
                                             prompt_len=len(r.prompt),
                                             budget=r.max_new_tokens)
                                self.metrics.histogram(
                                    "queue_wait_s").observe(t_admit - t0)
                            tok = self._sample_row(lg[j], i, 0)
                            last[j] = tok
                            if rec:
                                tracer.instant("first_token", "request",
                                               SLOT_TID0 + j, req_idx=i)
                                self.metrics.histogram("ttft_s").observe(
                                    time.perf_counter() - t0)
                            emit(j, tok, t_first)
                admitted = True
                if rec:
                    tracer.counter("slots_occupied",
                                   sum(s is not None for s in live))

            if all(s is None for s in live):
                break

            # Greedy slots with no live EOS can only leave the batch at a
            # known budget boundary: run the fused tick (step + argmax +
            # clock advance) up to that boundary with no host feedback, so
            # dispatches pipeline like the static engine's inner loop; one
            # settle then takes the whole span.  EOS-bearing or sampled
            # slots need per-tick feedback (k = 1).
            alive = [s for s in live if s is not None]
            if self.temperature <= 0.0 and all(s.eos_id < 0 for s in alive):
                k = min(s.budget - len(s.emitted) for s in alive)
            else:
                k = 1
            n_free = B - len(alive)
            with _phase_span(self, tracer, "engine.decode", fn=self._tick,
                             k=k, slots=len(alive)):
                t_span = time.perf_counter()
                with _phase_span(self, tracer, "engine.dispatch"):
                    if admitted or tok_dev is None:
                        # push the host mirrors once per refill round, not
                        # per tick
                        tok_dev = jnp.asarray(last[:, None], jnp.int32)
                        pos_dev = jnp.asarray(pos, jnp.int32)
                    pend = []
                    for _ in range(k):
                        tok_dev, logits, pos_dev, cache = self._tick(
                            self.params, tok_dev, pos_dev, cache)
                        pend.append(tok_dev)
                        self.batch_steps += 1
                        self.wasted_slot_steps += n_free
                with _phase_span(self, tracer, "engine.settle"):
                    if self.temperature <= 0.0:
                        span = [np.asarray(t)[:, 0] for t in pend]
                    else:
                        lg = np.asarray(logits)
                t_settled = time.perf_counter()
                if rec:
                    # every live slot got one token per tick, k ticks per
                    # span
                    itl = (t_settled - t_span) / k
                    hist = self.metrics.histogram("itl_s")
                    for _ in range(k * len(alive)):
                        hist.observe(itl)
                with _phase_span(self, tracer, "engine.emit"):
                    if self.temperature > 0.0:
                        # k == 1: per-slot RNG sampling overrides the argmax
                        toks = last.copy()
                        for j in range(B):
                            if live[j] is not None:
                                toks[j] = self._sample_row(
                                    lg[j], live[j].req_idx,
                                    len(live[j].emitted))
                        tok_dev = jnp.asarray(toks[:, None], jnp.int32)
                        span = [toks]
                    for toks in span:
                        for j in range(B):
                            if live[j] is None:
                                continue  # drained queue: slot decodes garbage
                            last[j] = toks[j]
                            emit(j, int(toks[j]), t_settled)
            pos += k
            if rec:
                tracer.counter("slots_occupied",
                               sum(s is not None for s in live))

        return results


def serve_continuous(model, params, requests: List[Request], batch_size: int,
                     cache_len: int, temperature: float = 0.0, seed: int = 0,
                     prefill_len: Optional[int] = None,
                     engine: Optional[ContinuousEngine] = None) -> List[Result]:
    """Continuous-batching scheduler (admission queue + mid-stream refill).
    Pass ``engine`` to reuse compiled executables across calls and to read
    the round/wasted-step counters afterwards — the engine's own cache_len/
    temperature/seed/prefill_len then apply and those arguments are
    ignored."""
    if engine is None:
        engine = ContinuousEngine(model, params, batch_size, cache_len,
                                  temperature, seed, prefill_len=prefill_len)
    else:
        _check_engine_batch(engine, batch_size)
    return engine.serve(requests)
