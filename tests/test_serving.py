"""Serving schedulers: per-request EOS/steps accounting, per-slot budgets, and
the ContinuousEngine (admission queue + mid-stream slot refill).

The load-bearing contract is the sequential-oracle parity: a greedy
ContinuousEngine queue must be TOKEN-IDENTICAL, request by request, to serving
each request alone.  The oracle pads every prompt to the engine's prefill
width and replicates it across all batch rows of the PR 1 fixed-batch engine
(same compiled shapes — bf16 results are only bit-stable at equal shapes), so
it goes through the old prefill + scalar-clock decode path: agreement proves
the per-slot clocks, the one-row slot prefill, and slot isolation together.

Fast tier runs small queues on a 2-layer model (exact + one table mode); the
full-size queues across cache families (local:global KV, SSM state, xLSTM
state) are ``slow`` and join the nightly job.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import build_model
from repro.serving.engine import (
    ContinuousEngine,
    DecodeEngine,
    Request,
    _trim_at_eos,
    serve,
    serve_continuous,
    serve_static,
)
from tests.test_archs import reduced


@pytest.fixture(scope="module")
def tiny_model():
    cfg = reduced("stablelm-3b").replace(n_layers=2)
    model = build_model(cfg)
    return model, model.init(jax.random.key(0))


def mixed_requests(rng, n, eos_every=3, lo_len=3, hi_len=9, lo_new=2, hi_new=8):
    """Mixed prompt lengths, budgets, and EOS ids (every ``eos_every``-th
    request gets a plausibly-sampled token id as its EOS)."""
    reqs = []
    for i in range(n):
        reqs.append(Request(
            prompt=rng.integers(0, 100, (int(rng.integers(lo_len, hi_len)),))
            .astype(np.int32),
            max_new_tokens=int(rng.integers(lo_new, hi_new)),
            eos_id=int(rng.integers(0, 128)) if i % eos_every == 1 else -1))
    return reqs


def sequential_oracle(model, params, batch_size, cache_len, prefill_len, req,
                      engine=None):
    """Serve ONE request through the fixed-batch engine, replicated across
    all rows at the continuous engine's prefill width; row 0 is the oracle."""
    if engine is None:
        engine = DecodeEngine(model, params, batch_size, cache_len)
    row = np.zeros((prefill_len,), np.int32)
    row[prefill_len - len(req.prompt):] = req.prompt
    gen, _ = engine.generate_batch(np.tile(row, (batch_size, 1)),
                                   req.max_new_tokens, req.eos_id)
    return _trim_at_eos(gen[0], req.max_new_tokens, req.eos_id)


class TestTrimAtEos:
    def test_cuts_after_first_eos_inclusive(self):
        t = np.asarray([4, 7, 9, 7, 1])
        np.testing.assert_array_equal(_trim_at_eos(t, 5, 7), [4, 7])
        np.testing.assert_array_equal(_trim_at_eos(t, 5, 1), t)
        np.testing.assert_array_equal(_trim_at_eos(t, 3, 1), [4, 7, 9])
        np.testing.assert_array_equal(_trim_at_eos(t, 5, -1), t)


class TestStaticAccounting:
    def test_eos_trims_tokens_and_steps(self, tiny_model):
        """Result.tokens must stop at the request's own first EOS; steps is
        the per-request generated count, not the batch-wide loop count."""
        model, params = tiny_model
        rng = np.random.default_rng(0)
        reqs = [Request(prompt=rng.integers(0, 100, (n,)).astype(np.int32),
                        max_new_tokens=6) for n in (4, 5)]
        base = serve_static(model, params, reqs, batch_size=2, cache_len=64)
        # rerun with req0's 3rd token as its EOS: same greedy prefix, so the
        # result must now be exactly tokens[:3] (EOS kept) with steps == 3
        eos0 = int(base[0].tokens[2])
        assert base[0].tokens[:2].tolist().count(eos0) == 0
        reqs[0].eos_id = eos0
        res = serve_static(model, params, reqs, batch_size=2, cache_len=64)
        np.testing.assert_array_equal(res[0].tokens, base[0].tokens[:3])
        assert res[0].steps == 3
        # req1 has no EOS: untouched by its neighbour's early stop
        np.testing.assert_array_equal(res[1].tokens, base[1].tokens)
        assert res[1].steps == 6

    def test_per_slot_budgets_stop_the_group_loop(self, tiny_model):
        """A group of [EOS-bearing request, exhausted-budget request] must
        stop decoding when the EOS fires — finished/dummy slots no longer
        drag the loop to the group-wide max budget."""
        model, params = tiny_model
        rng = np.random.default_rng(1)
        prompts = rng.integers(0, 100, (2, 4)).astype(np.int32)
        eng = DecodeEngine(model, params, 2, 64)
        gen, _ = eng.generate_batch(prompts, 8)
        eos0 = int(gen[0, 2])
        assert gen[0, :2].tolist().count(eos0) == 0
        eng.reset_counters()
        _, steps = eng.generate_batch(prompts, np.asarray([8, 1]),
                                      np.asarray([eos0, -1]))
        assert steps == 3  # slot 1 done at its budget, slot 0 at its EOS
        assert eng.batch_steps == 3

    def test_padding_slots_accounted_as_waste(self, tiny_model):
        """3 requests at batch 2: the dummy padding slot must not inflate
        per-request results, and the engine exposes the batch-wide counters
        separately from Result.steps."""
        model, params = tiny_model
        rng = np.random.default_rng(2)
        reqs = [Request(prompt=rng.integers(0, 100, (n,)).astype(np.int32),
                        max_new_tokens=5) for n in (3, 7, 5)]
        eng = DecodeEngine(model, params, 2, 64)
        res = serve_static(model, params, reqs, batch_size=2, cache_len=64,
                           engine=eng)
        assert len(res) == 3
        assert all(r.steps == len(r.tokens) == 5 for r in res)
        assert eng.batch_steps == 10  # two groups x 5 rounds
        # group 2's dummy slot sat done for rounds 2..5
        assert eng.wasted_slot_steps == 4

    def test_legacy_serve_alias(self):
        assert serve is serve_static


class TestContinuousEngine:
    def test_greedy_matches_sequential_oracle(self, tiny_model):
        """Acceptance: >= 8 mixed-length, mixed-EOS requests, token-identical
        to the per-request oracle, zero recompiles after the first refill."""
        model, params = tiny_model
        rng = np.random.default_rng(3)
        reqs = mixed_requests(rng, 8)
        S0 = max(len(r.prompt) for r in reqs)
        eng = ContinuousEngine(model, params, batch_size=2, cache_len=64)
        out = eng.serve(reqs)
        assert eng.refills >= 2
        counts = eng.compile_counts()
        if -1 not in counts.values():
            assert counts == {"prefill": 1, "decode_step": 1}, counts
        oracle = DecodeEngine(model, params, 2, 64)
        for i, r in enumerate(reqs):
            want = sequential_oracle(model, params, 2, 64, S0, r,
                                     engine=oracle)
            np.testing.assert_array_equal(out[i].tokens, want,
                                          err_msg=f"req {i}")
            assert out[i].steps == len(out[i].tokens)
            assert out[i].prompt_len == len(r.prompt)

    def test_greedy_matches_oracle_table_mode(self, tiny_model):
        """Same contract through the fused table-pack kernels (acceptance:
        at least one table mode besides exact)."""
        from repro.approx import ApproxConfig

        base, _ = tiny_model
        cfg = base.cfg.replace(
            approx=ApproxConfig(mode="table_pack", e_a=1e-4, omega=0.2))
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(4)
        reqs = mixed_requests(rng, 8, lo_new=2, hi_new=6)
        S0 = max(len(r.prompt) for r in reqs)
        eng = ContinuousEngine(model, params, batch_size=2, cache_len=64)
        out = eng.serve(reqs)
        assert eng.refills >= 2
        counts = eng.compile_counts()
        if -1 not in counts.values():
            assert counts == {"prefill": 1, "decode_step": 1}, counts
        oracle = DecodeEngine(model, params, 2, 64)
        for i, r in enumerate(reqs):
            want = sequential_oracle(model, params, 2, 64, S0, r,
                                     engine=oracle)
            np.testing.assert_array_equal(out[i].tokens, want,
                                          err_msg=f"req {i}")

    def test_refill_keeps_request_identity(self, tiny_model):
        """Results come back in queue order with each request's own prompt
        length and budget, across several refill generations."""
        model, params = tiny_model
        rng = np.random.default_rng(5)
        reqs = [Request(prompt=rng.integers(0, 100, (3 + i,)).astype(np.int32),
                        max_new_tokens=1 + (i % 4)) for i in range(9)]
        out = serve_continuous(model, params, reqs, batch_size=3, cache_len=64)
        for i, (r, res) in enumerate(zip(reqs, out)):
            assert res.prompt_len == len(r.prompt), i
            assert res.steps == len(res.tokens) == r.max_new_tokens, i

    def test_per_slot_rng_reproducible_and_slot_independent(self, tiny_model):
        """temperature > 0: a request's sampled tokens depend only on
        (engine seed, its queue index, its own logits) — identical across
        runs and across different slot assignments/admission times."""
        model, params = tiny_model
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, 100, (4,)).astype(np.int32)
                   for _ in range(3)]
        mk = lambda order, budgets: [
            Request(prompt=prompts[i], max_new_tokens=b)
            for i, b in zip(order, budgets)]
        a1 = serve_continuous(model, params, mk((0, 1, 2), (6, 2, 4)),
                              batch_size=2, cache_len=64, temperature=1.0,
                              seed=9)
        a2 = serve_continuous(model, params, mk((0, 1, 2), (6, 2, 4)),
                              batch_size=2, cache_len=64, temperature=1.0,
                              seed=9)
        for r1, r2 in zip(a1, a2):
            np.testing.assert_array_equal(r1.tokens, r2.tokens)
        # swap the first two requests: request 2 keeps its queue index but is
        # admitted into a different slot/time; its stream must not change
        b = serve_continuous(model, params, mk((1, 0, 2), (2, 6, 4)),
                             batch_size=2, cache_len=64, temperature=1.0,
                             seed=9)
        np.testing.assert_array_equal(a1[2].tokens, b[2].tokens)
        # and a different seed must actually change something
        c = serve_continuous(model, params, mk((0, 1, 2), (6, 2, 4)),
                             batch_size=2, cache_len=64, temperature=1.0,
                             seed=10)
        assert any(not np.array_equal(x.tokens, y.tokens)
                   for x, y in zip(a1, c))

    def test_wastes_no_more_than_static(self, tiny_model):
        """The serve-bench CI gate's deterministic half: on a staggered
        queue, continuous must strand fewer slot-rounds than static."""
        model, params = tiny_model
        rng = np.random.default_rng(7)
        reqs = [Request(prompt=rng.integers(0, 100, (6,)).astype(np.int32),
                        max_new_tokens=12 if i % 2 == 0 else 2)
                for i in range(8)]
        stat = DecodeEngine(model, params, 2, 64)
        serve_static(model, params, reqs, 2, 64, engine=stat)
        cont = ContinuousEngine(model, params, 2, 64)
        cont.serve(reqs)
        assert cont.wasted_fraction < stat.wasted_fraction
        assert cont.batch_steps < stat.batch_steps

    def test_zero_budget_matches_static(self, tiny_model):
        """max_new_tokens=0 yields an empty result in BOTH schedulers (it
        never occupies a continuous slot), so switching scheduler cannot
        conjure phantom tokens."""
        model, params = tiny_model
        rng = np.random.default_rng(9)
        reqs = [Request(prompt=rng.integers(0, 100, (4,)).astype(np.int32),
                        max_new_tokens=m) for m in (3, 0, 2, 0)]
        for res in (serve_static(model, params, reqs, 2, 64),
                    serve_continuous(model, params, reqs, 2, 64)):
            assert [r.steps for r in res] == [3, 0, 2, 0]
            assert res[1].tokens.size == 0 and res[3].tokens.size == 0

    @pytest.mark.parametrize("temperature", [0.0, 1.0])
    def test_on_token_fires_once_per_served_token(self, tiny_model,
                                                  temperature):
        """``on_token(req_idx, n, t)`` fires once per served token, in order
        from ``n == 0``, with non-decreasing settle times inside the serve
        call, never for a zero-budget request, and the tokens are those of a
        serve without the hook."""
        import time

        model, params = tiny_model
        reqs = mixed_requests(np.random.default_rng(11), 7)
        reqs.insert(3, Request(prompt=reqs[0].prompt, max_new_tokens=0))
        eng = ContinuousEngine(model, params, 2, 64, temperature=temperature,
                               seed=3)
        base = eng.serve(reqs)
        calls = {}
        t0 = time.perf_counter()
        out = eng.serve(reqs, on_token=lambda i, n, t: calls.setdefault(
            i, []).append((n, t)))
        t1 = time.perf_counter()
        for i, (a, b) in enumerate(zip(base, out)):
            np.testing.assert_array_equal(a.tokens, b.tokens)
            got = calls.get(i, [])
            assert [n for n, _ in got] == list(range(len(b.tokens))), i
            ts = [t for _, t in got]
            assert all(t0 <= x <= y <= t1 for x, y in zip(ts, ts[1:] + [t1]))
        assert 3 not in calls

    def test_engine_batch_size_mismatch_rejected(self, tiny_model):
        model, params = tiny_model
        eng = DecodeEngine(model, params, 2, 64)
        with pytest.raises(ValueError, match="batch size"):
            serve_static(model, params, [Request(np.zeros((2,), np.int32))],
                         batch_size=4, cache_len=64, engine=eng)

    def test_prompt_longer_than_prefill_len_rejected(self, tiny_model):
        model, params = tiny_model
        eng = ContinuousEngine(model, params, 2, 64, prefill_len=4)
        with pytest.raises(ValueError, match="exceeds the prefill width"):
            eng.serve([Request(prompt=np.zeros((6,), np.int32))])


class TestSlotPrefill:
    """Admission prefills each admitted request alone: one call of the
    engine's slot-addressed one-row prefill per request, written into its
    slot of the live cache in place."""

    @staticmethod
    def _prefill_slot(eng, cache, slot, prompt, width):
        rows = np.zeros((eng.B, width), np.int32)
        rows[slot, width - len(prompt):] = prompt
        return eng._prefill(eng.params, {"tokens": rows,
                                         "slot": np.int32(slot)}, cache)

    @pytest.mark.parametrize("slot", [0, 2])
    def test_refill_leaves_other_slots_bit_identical(self, tiny_model, slot):
        """Refilling one slot that has prefilled and decoded rewrites that
        slot's row of every cache leaf, positions included, to what a solo
        prefill gives, and leaves every other slot's rows bit-identical; the
        logits hold the solo prefill's row at the slot and zeros
        elsewhere."""
        model, params = tiny_model
        B, W, S0 = 3, 32, 8
        eng = ContinuousEngine(model, params, batch_size=B, cache_len=W)
        rng = np.random.default_rng(12)
        cache = model.init_cache(B, W)
        for j in range(B):  # every slot occupied, with prompts of its own
            _, cache = self._prefill_slot(
                eng, cache, j, rng.integers(1, 100, (S0,)).astype(np.int32),
                S0)
        tok, pos = jnp.ones((B, 1), jnp.int32), jnp.full((B,), S0, jnp.int32)
        for _ in range(2):  # two decode ticks move every slot's positions on
            tok, _, pos, cache = eng._tick(params, tok, pos, cache)
        before = jax.device_get(cache)
        prompt = rng.integers(1, 100, (5,)).astype(np.int32)
        logits, cache = self._prefill_slot(eng, cache, slot, prompt, S0)
        after = jax.device_get(cache)
        solo_row = np.zeros((1, S0), np.int32)
        solo_row[0, S0 - len(prompt):] = prompt
        solo_logits, solo = jax.device_get(jax.jit(model.prefill)(
            params, {"tokens": solo_row}, model.init_cache(1, W)))
        others = [j for j in range(B) if j != slot]
        for b, a, s, ax in zip(jax.tree.leaves(before), jax.tree.leaves(after),
                               jax.tree.leaves(solo),
                               jax.tree.leaves(eng._axes)):
            np.testing.assert_array_equal(np.take(a, others, axis=ax),
                                          np.take(b, others, axis=ax))
            np.testing.assert_array_equal(np.take(a, [slot], axis=ax), s)
            assert not np.array_equal(np.take(b, [slot], axis=ax), s)
        logits = np.asarray(logits)
        np.testing.assert_array_equal(logits[slot], solo_logits[0])
        assert not logits[others].any()

    def test_counters_keep_per_round_meaning(self, tiny_model):
        """The counters keep counting sampling work in rounds of B rows:
        ``prefill_rows`` counts the rows prefilled (one call per admitted
        request), ``prefills`` and ``batch_steps`` count each as 1/B of a
        round, so ``batch_steps - prefills`` is the decode ticks, and only a
        tick wastes slots.  Budgets 3, 5, 2, 4 and a zero-budget request on
        two slots admit 2 + 1 + 1 rows around 2 + 1 + 1 + 2 decode ticks,
        the last two with one slot drained."""
        model, params = tiny_model
        eng = ContinuousEngine(model, params, batch_size=2, cache_len=32)
        calls = []
        real = eng._prefill
        eng._prefill = lambda p, b, c: calls.append(int(b["slot"])) or \
            real(p, b, c)
        rng = np.random.default_rng(13)
        out = eng.serve([Request(prompt=rng.integers(1, 100, (6,)).astype(
            np.int32), max_new_tokens=m) for m in (3, 5, 2, 4, 0)])
        assert [r.steps for r in out] == [3, 5, 2, 4, 0]
        assert calls == [0, 1, 0, 0]
        assert eng.prefill_rows == len(calls) == 4
        assert eng.prefills == 4 / 2 and eng.refills == 2
        assert eng.batch_steps - eng.prefills == 6
        assert eng.wasted_slot_steps == 2
        assert eng.wasted_fraction == 2 / (2 * (6 + 4 / 2))
        eng.reset_counters()
        assert eng.prefill_rows == 0

    def test_refills_beyond_warm_slots_compile_nothing(self, tiny_model):
        """After a warm 2-request queue, a queue whose refills land in slots
        2 and 3 traces and compiles nothing: the slot is a traced operand,
        so two executables serve every slot and every number admitted."""
        model, params = tiny_model
        eng = ContinuousEngine(model, params, batch_size=4, cache_len=32,
                               prefill_len=8)
        eng.serve([Request(prompt=np.arange(1, 9, dtype=np.int32),
                           max_new_tokens=2) for _ in range(2)])
        slots = []
        real = eng._prefill
        eng._prefill = lambda p, b, c: slots.append(int(b["slot"])) or \
            real(p, b, c)
        events = []

        def on_event(name, secs, **kw):
            if name.startswith("/jax/core/compile/"):
                events.append(name)

        rng = np.random.default_rng(14)
        reqs = [Request(prompt=rng.integers(1, 100, (int(n),)).astype(
            np.int32), max_new_tokens=m)
            for n, m in zip((8, 3, 5, 7, 4, 6, 2, 8, 5),
                            (6, 5, 4, 2, 3, 2, 4, 3, 2))]
        jax.monitoring.register_event_duration_secs_listener(on_event)
        try:
            eng.serve(reqs)
        finally:
            jax.monitoring.unregister_event_duration_listener(on_event)
        assert set(slots[4:]) - {0, 1}, slots  # refills into slots 2 / 3
        assert events == []
        counts = eng.compile_counts()
        if -1 not in counts.values():
            assert counts == {"prefill": 1, "decode_step": 1}, counts

    def test_serves_repeat_and_keep_the_empty_cache(self, tiny_model):
        """The prefill donates the live cache, never the empty one every
        queue starts from: two serves in a row give the same tokens, and
        ``_fresh`` is still readable, and still empty, after them."""
        model, params = tiny_model
        eng = ContinuousEngine(model, params, batch_size=2, cache_len=32)
        reqs = mixed_requests(np.random.default_rng(15), 6)
        first = eng.serve(reqs)
        second = eng.serve(reqs)
        for a, b in zip(first, second):
            np.testing.assert_array_equal(a.tokens, b.tokens)
        fresh = jax.device_get(eng._fresh)
        empty = jax.device_get(model.init_cache(2, 32))
        for got, want in zip(jax.tree.leaves(fresh), jax.tree.leaves(empty)):
            np.testing.assert_array_equal(got, want)


@pytest.mark.slow
class TestContinuousAcrossFamilies:
    """Full-size queues through every cache family the engine can refill:
    local:global KV rings (gemma3), Mamba2 state + shared-attention KV
    (zamba2), positionless xLSTM state, and the quantized table mode."""

    @pytest.mark.parametrize("arch,mode", [
        ("gemma3-12b", "exact"),
        ("zamba2-1.2b", "exact"),
        ("xlstm-125m", "exact"),
        ("stablelm-3b", "quant_pack"),
    ])
    def test_oracle_parity_full_size(self, arch, mode):
        from repro.approx import ApproxConfig

        cfg = reduced(arch)
        if mode != "exact":
            cfg = cfg.replace(approx=ApproxConfig(mode=mode, e_a=1e-4,
                                                  omega=0.2))
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(8)
        reqs = mixed_requests(rng, 10, lo_len=3, hi_len=12, lo_new=2,
                              hi_new=12)
        S0 = max(len(r.prompt) for r in reqs)
        eng = ContinuousEngine(model, params, batch_size=3, cache_len=64)
        out = eng.serve(reqs)
        assert eng.refills >= 2
        counts = eng.compile_counts()
        if -1 not in counts.values():
            assert counts == {"prefill": 1, "decode_step": 1}, counts
        oracle = DecodeEngine(model, params, 3, 64)
        for i, r in enumerate(reqs):
            want = sequential_oracle(model, params, 3, 64, S0, r,
                                     engine=oracle)
            np.testing.assert_array_equal(out[i].tokens, want,
                                          err_msg=f"{arch}/{mode} req {i}")


class TestCounterLifecycle:
    """The batch/wasted-step counters and the engine metric registry across
    resets — the denominators the serve bench and ScopeKit report from."""

    def test_wasted_fraction_defined_at_zero_rounds(self, tiny_model):
        """A fresh engine (batch_steps == 0) reports wasted_fraction 0.0
        instead of dividing by zero, and serving an empty queue keeps it so."""
        model, params = tiny_model
        eng = ContinuousEngine(model, params, batch_size=2, cache_len=32)
        assert eng.batch_steps == 0
        assert eng.wasted_fraction == 0.0
        assert eng.serve([]) == []
        assert eng.batch_steps == 0 and eng.wasted_fraction == 0.0

    def test_reset_counters_clears_metrics_registry(self, tiny_model):
        """reset_counters() resets the engine's ScopeKit registry along with
        the integers, so warmup latencies never leak into a timed window."""
        from repro import obs

        model, params = tiny_model
        eng = ContinuousEngine(model, params, batch_size=2, cache_len=32)
        try:
            obs.configure(enabled=True)
            eng.serve(mixed_requests(np.random.default_rng(3), 3))
        finally:
            obs.disable()
        assert eng.metrics.summary()["histograms"]  # warmup recorded latencies
        assert eng.batch_steps > 0
        eng.reset_counters()
        assert eng.metrics.summary()["histograms"] == {}
        assert eng.batch_steps == 0 and eng.wasted_slot_steps == 0
        assert eng.compile_time_s == 0.0 and eng.wasted_fraction == 0.0


class TestRopeTableServing:
    """rope_table=True: rotary embeddings served from the folded trig tables
    (PR 8).  The contract is end-to-end — switching ONLY the rotary path from
    exact jnp sin/cos to the table-served fold must leave a greedy decode
    token-identical, and the fold must hold its error bound at the 128k
    positions a long-context cache would feed it."""

    def test_rope_table_token_identical_greedy(self, tiny_model):
        """Same arch, same params, same queue; the only delta between the two
        engines is apply_rope's sin_cos hook.  Greedy streams must match
        token for token through several refills.  At e_a=1e-6 the table trig
        lands within the model's bf16 resolution, so the rotated activations
        are bitwise identical and identity is exact, not probabilistic (at
        1e-4 a ~4e-3 logit wobble can flip a greedy tie some steps in)."""
        from repro.approx import ApproxConfig

        base, _ = tiny_model
        outs = []
        for rope_table in (False, True):
            cfg = base.cfg.replace(approx=ApproxConfig(
                mode="folded_pack_ref", e_a=1e-6, omega=0.2,
                rope_table=rope_table))
            model = build_model(cfg)
            assert (model.rope_sin_cos is not None) == rope_table
            params = model.init(jax.random.key(0))
            rng = np.random.default_rng(11)
            reqs = mixed_requests(rng, 6, lo_new=2, hi_new=6)
            eng = ContinuousEngine(model, params, batch_size=2, cache_len=64)
            outs.append(eng.serve(reqs))
        for i, (exact_r, table_r) in enumerate(zip(*outs)):
            np.testing.assert_array_equal(exact_r.tokens, table_r.tokens,
                                          err_msg=f"req {i}")
            assert exact_r.steps == table_r.steps

    def test_rope_parity_at_128k_positions(self):
        """apply_rope with the table hook vs exact, at positions up to 128k
        (angles deep in the Payne-Hanek regime for the base frequency).
        Rotation error is bounded by |x1|*d_cos + |x2|*d_sin <= 2*Ea'."""
        import jax.numpy as jnp

        from repro.approx import ApproxConfig
        from repro.models.common import apply_rope

        ea = 1e-4
        cfg = ApproxConfig(mode="folded_pack_ref", e_a=ea, rope_table=True)
        sc = cfg.rope_sin_cos()
        assert sc is not None
        positions = jnp.asarray(
            [[0, 1, 63, 4095, 65535, 131071, 131072]], jnp.int32)
        rng = np.random.default_rng(12)
        x = jnp.asarray(rng.uniform(-1, 1, (1, 7, 2, 16)), jnp.float32)
        exact = apply_rope(x, positions, 10_000.0)
        table = apply_rope(x, positions, 10_000.0, sin_cos=sc)
        tol = 2 * (ea * 1.02 + 1e-5)
        err = float(jnp.max(jnp.abs(exact - table)))
        assert err <= tol, f"max rotation err {err:.3e} > {tol:.3e}"
        # and the hook's raw trig is itself within the fold contract against
        # float64 numpy at the largest angles the positions produce
        ang = np.float32(131072.0)
        s, c = sc(jnp.full((1, 256), ang))
        bound = ea * 1.02 + 1e-5
        assert abs(float(s[0, 0]) - math.sin(float(ang))) <= bound
        assert abs(float(c[0, 0]) - math.cos(float(ang))) <= bound

    def test_rope_sin_cos_gating(self):
        """exact mode and rope_table=False both keep the exact path; an
        unknown mode with rope_table on raises instead of silently serving."""
        from repro.approx import ApproxConfig

        assert ApproxConfig(mode="exact", rope_table=True).rope_sin_cos() is None
        assert ApproxConfig(mode="folded_pack_ref").rope_sin_cos() is None
        with pytest.raises(ValueError, match="unknown approx mode"):
            ApproxConfig(mode="bogus", rope_table=True).rope_sin_cos()
