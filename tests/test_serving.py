"""Continuous-batching serving engine invariants."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core.serving import (PrefillJob, Request, ServeTimeoutError,
                                SynergyServer)
from repro.engines import CAP_EPILOGUE, CAP_GEMM, CAP_GRAD, CostModel, Engine
from repro.models import init_model
from repro.models.cnn import CNNConfig

#: a tiny conv front-end (MNIST topology at a fraction of the MACs) for
#: tests that run the REAL conv-as-GEMM prefill chain on slow sim engines
TINY_CNN = CNNConfig(
    name="tiny", input_hw=8, cin=1, layers=(
        ("conv", 4, 3, 1, 1), ("pool", 2),
        ("conv", 8, 3, 1, 1), ("fc", 10),
    ))


def _cfg():
    return reduced(ARCHS["granite-3-2b"], n_layers=2, d_model=32,
                   n_heads=2, d_ff=64, vocab=128)


def _server(slots=2, **kw):
    cfg = _cfg()
    params = init_model(cfg, jax.random.key(0))
    return SynergyServer(cfg, params, slots=slots, max_len=32,
                         prefill_len=4, **kw)


def test_all_requests_complete():
    srv = _server(slots=2)
    reqs = [Request(i, jax.random.randint(jax.random.key(i), (4,), 0, 128),
                    max_new_tokens=5) for i in range(5)]
    for r in reqs:
        srv.submit(r)
    stats = srv.run()
    assert all(len(r.out) >= 5 for r in reqs), [len(r.out) for r in reqs]
    assert stats.prefills == 5
    assert not srv.pending
    assert all(s is None for s in srv.slot_req)


def test_continuous_batching_overlaps_requests():
    """With more requests than slots, decode steps must serve multiple
    requests per step on average (slot_efficiency > 1)."""
    srv = _server(slots=2)
    for i in range(4):
        srv.submit(Request(i, jnp.arange(4, dtype=jnp.int32) + i,
                           max_new_tokens=6))
    stats = srv.run()
    assert stats.slot_efficiency > 1.0, stats


def test_engine_idle_returns_false():
    srv = _server()
    assert srv.step() is False


def test_prefill_does_not_corrupt_live_requests():
    """Regression: prefill used to broadcast the new prompt's tokens into
    EVERY slot's KV cache; a live request's generation changed whenever
    another request was admitted.  Prefill must write only the target
    slot, so a request's output is identical with or without a
    mid-generation admission."""
    prompt = jax.random.randint(jax.random.key(7), (4,), 0, 128)

    solo = _server(slots=2)
    ra = Request(0, prompt, max_new_tokens=8)
    solo.submit(ra)
    solo.run()

    staggered = _server(slots=2)
    rb = Request(0, prompt, max_new_tokens=8)
    staggered.submit(rb)
    staggered.step()                       # prefill A
    staggered.step(); staggered.step()     # 2 decode steps
    other = Request(1, jax.random.randint(jax.random.key(9), (4,), 0, 128),
                    max_new_tokens=8)
    staggered.submit(other)                # admitted mid-generation
    staggered.run()

    assert rb.out == ra.out, "another request's prefill changed A's tokens"
    assert len(other.out) >= 8


def test_decode_uses_per_slot_positions():
    """Slots prefilled at different times decode at their own positions:
    a request's output must not depend on its slot's admission order."""
    prompt = jnp.arange(4, dtype=jnp.int32)
    first = _server(slots=2)
    r1 = Request(0, prompt, max_new_tokens=6)
    first.submit(r1)
    first.run()

    late = _server(slots=2)
    filler = Request(7, jnp.arange(4, dtype=jnp.int32) + 3,
                     max_new_tokens=3)
    late.submit(filler)
    late.step()                  # filler occupies slot 0, advances its pos
    late.step(); late.step()
    r2 = Request(0, prompt, max_new_tokens=6)
    late.submit(r2)              # prefills into a DIFFERENT slot state
    late.run()
    assert r2.out == r1.out


def test_prefill_does_not_corrupt_live_ssm_state():
    """Same isolation guarantee for recurrent (Mamba) caches: bystander
    slots' SSM state is masked during prefill, and a reused slot's state
    is reset (attention masks stale K/V, but a recurrence would otherwise
    continue from the previous request)."""
    cfg = reduced(ARCHS["mamba2-130m"], n_layers=2, d_model=32, vocab=128)
    params = init_model(cfg, jax.random.key(0))

    def mk():
        return SynergyServer(cfg, params, slots=2, max_len=32,
                             prefill_len=4)

    prompt = jnp.arange(4, dtype=jnp.int32)
    solo = mk()
    ra = Request(0, prompt, max_new_tokens=6)
    solo.submit(ra)
    solo.run()

    staggered = mk()
    rb = Request(0, prompt, max_new_tokens=6)
    staggered.submit(rb)
    staggered.step(); staggered.step(); staggered.step()
    staggered.submit(Request(1, jnp.arange(4, dtype=jnp.int32) + 7,
                             max_new_tokens=6))
    staggered.run()
    assert rb.out == ra.out

    # slot reuse: 3 identical prompts through 2 slots; the third (reused
    # slot) must decode the same tokens as the first
    reuse = mk()
    reqs = [Request(i, prompt, max_new_tokens=5) for i in range(3)]
    for r in reqs:
        reuse.submit(r)
    reuse.run()
    assert reqs[2].out == reqs[0].out


def test_serving_jobs_route_through_dispatcher():
    srv = _server(slots=2)
    for i in range(3):
        srv.submit(Request(i, jnp.arange(4, dtype=jnp.int32) + i,
                           max_new_tokens=4))
    stats = srv.run()
    assert stats.job_engine.keys() == {"prefill", "decode"}
    assert stats.job_busy_s["prefill"] > 0
    assert stats.job_busy_s["decode"] > 0


# ------------------------------------------------------- admission waves

def test_wave_admission_admits_min_pending_free():
    """N pending requests + M free slots admit min(N, M) in ONE step."""
    srv = _server(slots=3)
    for i in range(5):
        srv.submit(Request(i, jnp.arange(4, dtype=jnp.int32) + i,
                           max_new_tokens=4))
    assert srv.step() is True
    assert srv.stats.prefills == 3          # min(5 pending, 3 free)
    assert srv.stats.prefill_waves == 1
    assert len(srv.pending) == 2
    assert all(r is not None for r in srv.slot_req)
    # no free slot -> the next step decodes instead of admitting
    srv.step()
    assert srv.stats.prefills == 3
    assert srv.stats.decode_steps == 1
    stats = srv.run()
    assert stats.prefills == 5
    # 5 requests through 3 slots took at most 3 waves
    assert stats.prefill_waves <= 3


def test_single_admission_mode_admits_one_per_step():
    srv = _server(slots=3, admission="single")
    for i in range(3):
        srv.submit(Request(i, jnp.arange(4, dtype=jnp.int32) + i,
                           max_new_tokens=4))
    srv.step()
    assert srv.stats.prefills == 1
    stats = srv.run()
    assert stats.prefills == 3
    assert stats.prefill_waves == 3


def test_wave_admission_outputs_match_single_admission():
    """Batching the admission wave must not change any request's tokens:
    per-slot masked positions keep the batched LM replay equal to the
    one-request-at-a-time replay."""
    reqs = lambda: [Request(i, jnp.arange(4, dtype=jnp.int32) * (i + 1) % 128,
                            max_new_tokens=6) for i in range(4)]
    wave, single = _server(slots=2), _server(slots=2, admission="single")
    ra, rb = reqs(), reqs()
    for r in ra:
        wave.submit(r)
    for r in rb:
        single.submit(r)
    wave.run()
    single.run()
    assert [r.out for r in ra] == [r.out for r in rb]


def test_wave_slot_reuse_stays_corruption_free():
    """The PR 1 masked-KV regression, extended to the batched admission
    path: 3 identical prompts through 2 slots (the third rides a REUSED
    slot admitted in a second wave) decode identical tokens."""
    from repro.soc import SynergyRuntime
    prompt = jnp.arange(4, dtype=jnp.int32)
    with SynergyRuntime(["F-PE", "S-PE"], name="reuse") as rt:
        srv = _server(slots=2, runtime=rt, prefill_cnn=TINY_CNN)
        reqs = [Request(i, prompt, max_new_tokens=5) for i in range(3)]
        for r in reqs:
            srv.submit(r)
        srv.run()
    assert reqs[2].out == reqs[0].out
    assert reqs[1].out == reqs[0].out


# ------------------------------------------------- real conv-as-GEMM prefill

def test_prefill_jobsets_are_real_conv_shapes():
    """No proxy GEMM left: the wave's JobSets are the conv-as-GEMM shapes
    of the paper CNN (k = kh*kw*cin, n = cout, m = frames*oh*ow), exactly
    what build_simnet exports to the DES."""
    from repro.models.cnn import conv_jobsets
    cfg = _cfg()
    job = PrefillJob(wave=1, rids=(0, 1), slots=(0, 1), n_frames=8,
                     cnn=TINY_CNN)
    jss = job.jobsets()
    expected = [js for _, js in conv_jobsets(TINY_CNN, 8)]
    assert [(js.m, js.n, js.k) for js in jss] \
        == [(js.m, js.n, js.k) for js in expected]
    # conv0: 8 frames x 8x8 spatial, 3x3x1 patch, 4 filters
    assert (jss[0].m, jss[0].n, jss[0].k) == (8 * 8 * 8, 4, 9)
    # the old proxy (m = tokens*layers, k = d_model) is gone
    assert all(js.k != cfg.d_model for js in jss)
    assert all("conv" in js.name for js in jss)


def test_prefill_busy_seconds_match_conv_cost_model():
    """ServeStats prefill busy-seconds == the conv cost model's estimate
    of the wave's jobsets, on BOTH dispatch paths (single-engine runtime
    split and dispatcher-routed accounting)."""
    from repro.engines import get_engine
    from repro.models.cnn import conv_jobsets
    from repro.soc import SynergyRuntime

    def expected_busy(eng, n_frames):
        return sum(eng.estimate(js)
                   for _, js in conv_jobsets(TINY_CNN, n_frames))

    # runtime path: single F-PE pool -> every panel booked at F-PE rates
    with SynergyRuntime(["F-PE"], name="busy") as rt:
        srv = _server(slots=2, runtime=rt, prefill_cnn=TINY_CNN)
        for i in range(2):
            srv.submit(Request(i, jnp.arange(4, dtype=jnp.int32) + i,
                               max_new_tokens=2))
        stats = srv.run()
    exp = expected_busy(get_engine("F-PE"), n_frames=8)   # 2 reqs x 4 toks
    assert stats.job_busy_s["prefill"] == pytest.approx(exp, rel=1e-6)

    # dispatcher path books the selected engine's estimate of the same sets
    srv2 = _server(slots=2, prefill_cnn=TINY_CNN)
    srv2.submit(Request(0, jnp.arange(4, dtype=jnp.int32),
                        max_new_tokens=2))
    stats2 = srv2.run()
    eng = srv2.dispatcher.select(
        PrefillJob(1, (0,), (0,), 4, TINY_CNN).jobsets()[0],
        job_class="prefill")
    exp2 = expected_busy(eng, n_frames=4)
    assert stats2.job_busy_s["prefill"] == pytest.approx(exp2, rel=1e-6)


def test_wave_prefill_gathers_im2col_once_per_layer(monkeypatch):
    """Satellite: ONE im2col gather per conv layer covers the whole
    admission wave — not one gather per request."""
    import repro.core.serving as serving_mod
    calls = []
    real = serving_mod.im2col_wave

    def counting(x, *a, **kw):
        calls.append(int(x.shape[0]))
        return real(x, *a, **kw)

    monkeypatch.setattr(serving_mod, "im2col_wave", counting)
    from repro.soc import SynergyRuntime
    with SynergyRuntime(["F-PE", "S-PE"], name="gather") as rt:
        srv = _server(slots=3, runtime=rt, prefill_cnn=TINY_CNN)
        for i in range(3):
            srv.submit(Request(i, jnp.arange(4, dtype=jnp.int32) + i,
                               max_new_tokens=2))
        assert srv.step() is True      # one wave admits all 3
        srv.drain()
    n_conv = sum(1 for spec in TINY_CNN.layers if spec[0] == "conv")
    assert len(calls) == n_conv        # NOT 3 * n_conv
    assert calls[0] == 12              # 3 requests x 4 frames, one batch


# ------------------------------------------- coalesced decode: bitwise

def _run_decode_mode(mode, engines, n_req=3, cnn=TINY_CNN, **server_kw):
    from repro.soc import SynergyRuntime
    cfg = _cfg()
    params = init_model(cfg, jax.random.key(0))
    with SynergyRuntime(engines, name=f"bitwise-{mode}") as rt:
        srv = SynergyServer(cfg, params, slots=2, max_len=32, prefill_len=4,
                            runtime=rt, prefill_cnn=cnn, decode_mode=mode,
                            keep_decode_outputs=True, max_inflight=1,
                            **server_kw)
        reqs = [Request(i, jnp.arange(4, dtype=jnp.int32) + i,
                        max_new_tokens=5) for i in range(n_req)]
        for r in reqs:
            srv.submit(r)
        stats = srv.run()
    return reqs, stats, srv.decode_gemm_outputs


def test_batched_decode_bitwise_identical_fp32():
    """The coalesced (live*n_layers, d) @ (d, 4d) decode submission is
    BITWISE identical to the sequential per-slot loop on the fp32 path
    (row reductions are row-independent)."""
    ra, sa, outs_a = _run_decode_mode("batched", ["F-PE", "S-PE"])
    rb, sb, outs_b = _run_decode_mode("per-slot", ["F-PE", "S-PE"])
    assert [r.out for r in ra] == [r.out for r in rb]
    assert sa.decode_steps == sb.decode_steps
    assert len(outs_a) == sa.decode_steps and len(outs_b) == sb.decode_steps
    for ya, yb in zip(outs_a, outs_b):
        assert ya.shape == yb.shape    # (live, n_layers, 4*d_model)
        assert np.array_equal(np.asarray(ya), np.asarray(yb))
    # batched mode coalesces: one submission per step, fewer padded tiles
    assert sa.runtime_jobs < sb.runtime_jobs


def test_batched_decode_bitwise_identical_int8_calibrated():
    """Same bitwise identity on the int8-calibrated path: panels carry
    exact int32 partials and both modes feed the calibrator ONCE per step
    at reap (batch-shape keyed), so scale trajectories — and therefore
    quantized outputs — are identical."""
    from repro.engines import get_engine
    from repro.quant import QuantizedEngine

    def mk_engine(tag):
        return QuantizedEngine(get_engine("xla"), name=f"bw-int8-{tag}")

    qa = mk_engine("batched")
    ra, sa, outs_a = _run_decode_mode("batched", [qa])
    qb = mk_engine("per-slot")
    rb, sb, outs_b = _run_decode_mode("per-slot", [qb])
    assert [r.out for r in ra] == [r.out for r in rb]
    # the calibrator saw one observation per decode step in BOTH modes;
    # the key is the real n-stacked FFN GEMM shape (d, n_layers·2·d_ff)
    cfg = _cfg()
    key = (cfg.d_model, cfg.n_layers * 2 * cfg.d_ff)
    assert qa.calibrator.state()[key].updates == sa.decode_steps
    assert qb.calibrator.state()[key].updates == sb.decode_steps
    assert qa.calibrator.state()[key].amax \
        == qb.calibrator.state()[key].amax
    assert qa.act_scale_for(*key) is not None
    assert len(outs_a) == len(outs_b) > 1
    for ya, yb in zip(outs_a, outs_b):
        assert np.array_equal(np.asarray(ya), np.asarray(yb))
    # decode really ran on the quantized engine
    assert sa.precision_jobs["int8"] > 0


# --------------------------------------------------- async in-flight window

def test_inflight_window_overlaps_and_orders_completions():
    from repro.soc import SynergyRuntime
    with SynergyRuntime(["F-PE", "S-PE"], name="window") as rt:
        srv = _server(slots=2, runtime=rt, prefill_cnn=TINY_CNN,
                      max_inflight=4)
        for i in range(4):
            srv.submit(Request(i, jnp.arange(4, dtype=jnp.int32) + i,
                               max_new_tokens=4))
        stats = srv.run()
    assert stats.inflight_peak > 1         # submissions overlapped steps
    assert not srv._inflight               # run() drained the window
    assert stats.runtime_jobs > 0
    assert rt.stats()["total_jobs"] == stats.runtime_jobs


class _SleepyEngine(Engine):
    """Deterministically slow engine: every panel sleeps, so a tiny
    submit_timeout trips mid-prefill."""

    def __init__(self, name="sleepy", delay_s=0.2):
        super().__init__(name, {CAP_GEMM, CAP_EPILOGUE, CAP_GRAD},
                         cost=CostModel(macs_per_s=1e9))
        self.delay_s = delay_s

    def execute(self, a, b, *, bias=None, activation=None,
                tile=(256, 256, 256), out_dtype=None, precision=None):
        time.sleep(self.delay_s)
        y = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))
        if bias is not None:
            y = y + bias
        if activation is not None:
            y = activation(y)
        return y.astype(out_dtype or a.dtype)


def test_submit_timeout_surfaces_serve_timeout_error():
    """Satellite: the hard-coded 60s futures wait is gone — the timeout is
    a constructor arg and tripping it raises ServeTimeoutError naming the
    jobset (not a bare TimeoutError)."""
    from repro.soc import SynergyRuntime
    with SynergyRuntime([_SleepyEngine()], name="slowpool") as rt:
        srv = _server(slots=1, runtime=rt, prefill_cnn=TINY_CNN,
                      submit_timeout=0.01)
        srv.submit(Request(0, jnp.arange(4, dtype=jnp.int32),
                           max_new_tokens=2))
        with pytest.raises(ServeTimeoutError) as ei:
            srv.run()
    assert "prefill/w1" in str(ei.value)
    assert ei.value.timeout == 0.01


def test_timeout_cancels_graph_and_drains_queues():
    """Satellite 1: tripping submit_timeout on a prefill graph CANCELS
    it — not-yet-started downstream nodes never launch, queued panels are
    drained — and the pool immediately serves fresh work instead of
    grinding through the dead wave's backlog."""
    from repro.core.job import JobSet
    from repro.soc import GraphCancelled, SynergyRuntime
    eng = _SleepyEngine(delay_s=0.2)
    with SynergyRuntime([eng], name="slowpool2") as rt:
        srv = _server(slots=1, runtime=rt, prefill_cnn=TINY_CNN,
                      submit_timeout=0.01)
        captured = {}
        orig = rt.submit_graph

        def capture(*a, **kw):
            gf = orig(*a, **kw)
            captured["gf"] = gf
            return gf

        rt.submit_graph = capture
        srv.submit(Request(0, jnp.arange(4, dtype=jnp.int32),
                           max_new_tokens=2))
        with pytest.raises(ServeTimeoutError):
            srv.run()
        gf = captured["gf"]
        with pytest.raises((GraphCancelled, RuntimeError)):
            gf.result(10)
        states = gf.node_states()
        assert "cancelled" in states       # downstream never started
        assert "done" not in states[-1:] or states[-1] == "cancelled"
        # queues drained: fresh work completes in ~one panel delay, far
        # less than the cancelled wave's remaining serial backlog
        a = jnp.ones((16, 32), jnp.float32)
        b = jnp.ones((32, 16), jnp.float32)
        t0 = time.monotonic()
        rt.submit_gemm(a, b, jobset=JobSet.for_gemm(9, 16, 16, 32, 16,
                                                    name="fresh"),
                       tile=(16, 16, 16)).result(30)
        assert time.monotonic() - t0 < 1.0


# ------------------------------------------------------- chunked prefill

def test_chunked_prefill_interleaves_decode_and_matches_blocking():
    """Tentpole: with ``prefill_chunk_macs`` set, admission work is split
    into bounded chunks interleaved with decode — live decoders never
    stall behind a wave (decode_stall_steps == 0) — and every request's
    token stream is IDENTICAL to the legacy blocking admission (replay
    quanta touch only the wave's slots, decode only live slots)."""
    def run(**kw):
        srv = _server(slots=2, **kw)
        reqs = [Request(i, jnp.arange(4, dtype=jnp.int32) + i,
                        max_new_tokens=3 + i) for i in range(4)]
        for r in reqs:
            srv.submit(r)
        stats = srv.run()
        return [list(r.out) for r in reqs], stats

    outs_blk, st_blk = run()
    outs_chk, st_chk = run(prefill_chunk_macs=20_000)
    assert outs_chk == outs_blk                     # bitwise token parity
    assert st_chk.prefill_chunks > 0
    assert st_chk.decode_stall_steps == 0           # decode ran every step
    assert st_blk.prefill_chunks == 0               # legacy mode untouched
    # staggered completions force an admission while a decoder is live:
    # the blocking server stalls it, the chunked one never does
    assert st_blk.decode_stall_steps > 0
    assert st_chk.prefills == st_blk.prefills == 4


def test_chunked_conv_graph_chunks_through_runtime():
    """The wave's conv front-end splits into multiple bounded-MAC graph
    chunks chained by their carry, still producing the same tokens as one
    unchunked graph, with all conv jobs booked."""
    from repro.soc import SynergyRuntime

    def run(chunk):
        with SynergyRuntime(["F-PE", "S-PE"], name=f"chunk{chunk}") as rt:
            srv = _server(slots=2, runtime=rt, prefill_cnn=TINY_CNN,
                          prefill_chunk_macs=chunk)
            reqs = [Request(i, jnp.arange(4, dtype=jnp.int32) + i,
                            max_new_tokens=3 + i) for i in range(4)]
            for r in reqs:
                srv.submit(r)
            stats = srv.run()
        return [list(r.out) for r in reqs], stats

    outs_one, st_one = run(None)
    # ~147k MACs per TINY_CNN conv layer at 8 frames: one layer per chunk
    outs_many, st_many = run(150_000)
    assert outs_many == outs_one
    assert st_many.prefill_chunks >= 4     # >= 2 conv chunks x 2 waves
    assert st_many.decode_stall_steps == 0
    assert st_many.prefills == st_one.prefills == 4
    # chunking never drops conv work (busy-SECONDS are steal-placement
    # dependent across F-PE/S-PE, so compare booked work, not seconds)
    assert st_many.runtime_jobs > 0
    assert st_many.job_busy_s["prefill"] > 0


def test_run_serves_every_request_while_a_conv_chain_lags(monkeypatch):
    """A conv chunk that finishes after the live decoders (a loaded host)
    leaves a step with nothing to do but wait: ``run()`` waits for the
    chain and admits the queued requests instead of returning without
    them."""
    from repro.core import serving
    from repro.soc import SynergyRuntime

    advance, polls = serving.SynergyServer._advance_conv, {}

    def lagging(self, conv):
        if conv is not None and conv.fut is not None:
            polls[id(conv.fut)] = polls.get(id(conv.fut), 0) + 1
            if polls[id(conv.fut)] <= 40:
                return False
        return advance(self, conv)

    monkeypatch.setattr(serving.SynergyServer, "_advance_conv", lagging)
    with SynergyRuntime(["F-PE", "S-PE"], name="lagging") as rt:
        srv = _server(slots=2, runtime=rt, prefill_cnn=TINY_CNN,
                      prefill_chunk_macs=150_000)
        reqs = [Request(i, jnp.arange(4, dtype=jnp.int32) + i,
                        max_new_tokens=3 + i) for i in range(4)]
        for r in reqs:
            srv.submit(r)
        stats = srv.run()
    assert [len(r.out) for r in reqs] == [3, 4, 5, 6]
    assert stats.prefills == 4


# ------------------------------------------------- real FFN decode weights

def test_decode_weight_stacks_real_ffn_layers():
    """Satellite 2: dense-family params expose blocks.mlp.wi of shape
    (n_layers, d_model, 2·d_ff) — the decode GEMM weight is the REAL
    per-layer wi stacked along n, not the seeded proxy."""
    cfg = _cfg()
    params = init_model(cfg, jax.random.key(0))
    srv = SynergyServer(cfg, params, slots=2, max_len=32, prefill_len=4)
    assert srv._decode_ffn_cols == 2 * cfg.d_ff
    assert srv._decode_w.shape == (cfg.d_model,
                                   cfg.n_layers * 2 * cfg.d_ff)
    wi = params["blocks"]["mlp"]["wi"]
    ref = jnp.transpose(wi, (1, 0, 2)).reshape(cfg.d_model, -1)
    assert np.array_equal(np.asarray(srv._decode_w),
                          np.asarray(ref.astype(jnp.float32)))


def test_decode_weight_proxy_fallback_for_ssm():
    """Families without a dense FFN stack (mamba blocks) fall back to the
    (d_model, 4·d_model) proxy — and still serve end to end."""
    cfg = reduced(ARCHS["mamba2-130m"])
    params = init_model(cfg, jax.random.key(0))
    srv = SynergyServer(cfg, params, slots=1, max_len=16, prefill_len=2)
    assert srv._decode_ffn_cols is None
    assert srv._decode_w.shape == (cfg.d_model, 4 * cfg.d_model)
    req = Request(0, jnp.arange(2, dtype=jnp.int32) % cfg.vocab_size,
                  max_new_tokens=2)
    srv.submit(req)
    stats = srv.run()
    assert stats.decode_steps >= 1 and len(req.out) >= 2


def test_empty_prompt_mid_wave_drops_nothing():
    """A bad request mid-wave must fail BEFORE any wave member is popped:
    the earlier requests stay pending and get served on retry."""
    srv = _server(slots=2)
    good = Request(0, jnp.arange(4, dtype=jnp.int32), max_new_tokens=3)
    bad = Request(1, jnp.zeros((0,), jnp.int32), max_new_tokens=3)
    srv.submit(good)
    srv.submit(bad)
    with pytest.raises(ValueError, match="empty prompt"):
        srv.step()
    assert srv.pending and srv.pending[0] is good   # nothing was dropped
    assert all(r is None for r in srv.slot_req)
    srv.pending.remove(bad)
    stats = srv.run()
    assert stats.prefills == 1 and len(good.out) >= 3
