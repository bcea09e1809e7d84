"""One benchmark per paper table/figure (Synergy, 2018).

Each function returns (rows, derived_summary).  The DES (calibrated in
repro.core.clusters) reproduces the paper's runtime; see EXPERIMENTS.md
§Paper-validation for measured-vs-paper numbers.
"""

from __future__ import annotations

import statistics
import sys
import os

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.paper_cnns import PAPER_CNNS                 # noqa: E402
from repro.core.clusters import (Cluster, F_PE, NEON, S_PE,     # noqa: E402
                                 default_synergy_clusters)
from repro.core.scheduler import (search_sc, simulate,          # noqa: E402
                                  single_thread_latency)
from repro.models.cnn import build_simnet, cnn_flops_per_frame  # noqa: E402

FRAMES = 96

# power model from the paper's measurements (§4.1)
P_SYNERGY_W = 2.08
P_CPU_W = 1.52


def _nets():
    return {name: build_simnet(cfg) for name, cfg in PAPER_CNNS.items()}


def fig9_throughput():
    """Fig 9: Synergy throughput speedup over single-threaded Darknet."""
    rows = []
    for name, net in _nets().items():
        st = single_thread_latency(net)
        ws = simulate(net, policy="ws", frames=FRAMES)
        rows.append({"net": name, "fps": ws.fps, "single_thread_ms": st * 1e3,
                     "speedup": ws.fps * st})
    mean = statistics.mean(r["speedup"] for r in rows)
    return rows, {"mean_speedup": mean, "paper": 7.3}


def _config_only(n_fpe=0, n_spe=0, n_neon=0):
    accs = ([F_PE(i) for i in range(n_fpe)] + [S_PE(i) for i in range(n_spe)]
            + [NEON(i) for i in range(n_neon)])
    return [Cluster("only", tuple(accs))]


def fig11_latency_heterogeneity():
    """Fig 11: non-pipelined latency — CPU+NEON vs CPU+FPGA vs CPU+Het.

    The paper's non-pipelined designs are single-threaded hosts driving the
    whole accelerator pool, so each config is ONE cluster (a two-cluster
    split would add a slow-NEON straggler tail that the paper's setup does
    not have)."""
    rows = []
    for name, net in _nets().items():
        neon = simulate(net, _config_only(n_neon=2), policy="ws",
                        frames=24, pipelined=False)
        fpga = simulate(net, _config_only(n_fpe=6, n_spe=2), policy="ws",
                        frames=24, pipelined=False)
        het = simulate(net, _config_only(n_fpe=6, n_spe=2, n_neon=2),
                       policy="ws", frames=24, pipelined=False)
        rows.append({"net": name, "neon_ms": neon.latency_s * 1e3,
                     "fpga_ms": fpga.latency_s * 1e3,
                     "het_ms": het.latency_s * 1e3,
                     "het_vs_fpga": fpga.latency_s / het.latency_s - 1})
    mean = statistics.mean(r["het_vs_fpga"] for r in rows)
    return rows, {"mean_het_latency_gain": mean, "paper": 0.12}


def fig12_throughput_heterogeneity():
    """Fig 12: pipelined throughput — same comparison."""
    rows = []
    for name, net in _nets().items():
        fpga = simulate(net, _config_only(n_fpe=6, n_spe=2), policy="ws",
                        frames=FRAMES)
        het = simulate(net, default_synergy_clusters(), policy="ws",
                       frames=FRAMES)
        rows.append({"net": name, "fpga_fps": fpga.fps, "het_fps": het.fps,
                     "het_vs_fpga": het.fps / fpga.fps - 1})
    mean = statistics.mean(r["het_vs_fpga"] for r in rows)
    return rows, {"mean_het_throughput_gain": mean, "paper": 0.15}


def fig13_work_stealing():
    """Fig 13: WS vs static-fixed (SF) vs static-custom (SC)."""
    rows = []
    for name, net in _nets().items():
        sf = simulate(net, policy="sf", frames=FRAMES)
        _, _, sc = search_sc(net, frames=64)
        ws = simulate(net, policy="ws", frames=FRAMES)
        rows.append({"net": name, "sf_fps": sf.fps, "sc_fps": sc.fps,
                     "ws_fps": ws.fps,
                     "ws_vs_sf": ws.fps / sf.fps - 1,
                     "ws_vs_sc": ws.fps / sc.fps - 1})
    return rows, {
        "mean_ws_vs_sf": statistics.mean(r["ws_vs_sf"] for r in rows),
        "mean_ws_vs_sc": statistics.mean(r["ws_vs_sc"] for r in rows),
        "paper": {"ws_vs_sf": 0.24, "ws_vs_sc": 0.06}}


def fig14_cluster_balance():
    """Fig 14: per-cluster busy time per frame, SF vs WS (CIFAR_Alex)."""
    net = build_simnet(PAPER_CNNS["CIFAR_Alex"])
    sf = simulate(net, policy="sf", frames=FRAMES)
    ws = simulate(net, policy="ws", frames=FRAMES)
    imb = lambda d: max(d.values()) / max(min(d.values()), 1e-9)
    rows = [{"design": "SF", **{k: v * 1e3 for k, v in
                                sf.per_cluster_runtime.items()}},
            {"design": "Synergy", **{k: v * 1e3 for k, v in
                                     ws.per_cluster_runtime.items()}}]
    return rows, {"sf_imbalance": imb(sf.per_cluster_runtime),
                  "ws_imbalance": imb(ws.per_cluster_runtime),
                  "paper": {"sf": 24.3 / 12.3, "ws": 22.2 / 20.9}}


def table6_utilization():
    """Table 6: accelerator cluster utilization across designs."""
    rows = []
    for name, net in _nets().items():
        np_ = simulate(net, policy="ws", frames=24, pipelined=False)
        sf = simulate(net, policy="sf", frames=FRAMES)
        _, _, sc = search_sc(net, frames=64)
        ws = simulate(net, policy="ws", frames=FRAMES)
        rows.append({"net": name, "non_pipelined": np_.utilization,
                     "sf": sf.utilization, "sc": sc.utilization,
                     "synergy": ws.utilization})
    mean = {k: statistics.mean(r[k] for r in rows)
            for k in ("non_pipelined", "sf", "sc", "synergy")}
    return rows, {"mean": mean,
                  "paper": {"non_pipelined": 0.5605, "sf": 0.9246,
                            "sc": 0.9647, "synergy": 0.9980}}


def fig7_mmu_contention():
    """Fig 7: single-MMU vs multi-MMU scaling (queueing model analog).

    A PE's job has a memory phase (tile fetch/writeback through the MMU)
    and a compute phase.  With ONE MMU the memory phases serialize across
    PEs; with one MMU per 2 PEs they only pairwise serialize — per-job
    service time grows as max(compute, contenders * mem)."""
    mem_frac, comp_frac = 0.35, 0.65
    rows = []
    for n_pe in range(1, 9):
        single = n_pe / max(comp_frac, n_pe * mem_frac)
        multi = n_pe / max(comp_frac, 2 * mem_frac)
        rows.append({"n_pe": n_pe, "single_mmu_speedup": single,
                     "multi_mmu_speedup": multi})
    return rows, {"single_mmu_saturates_at": max(
        r["single_mmu_speedup"] for r in rows),
        "multi_mmu_linear": rows[-1]["multi_mmu_speedup"] > 6.5}


def table3_4_energy():
    """Tables 3/4: energy per frame and GOPS/W (power-model proxy:
    measured board powers from the paper x simulated frame times)."""
    rows = []
    for name, cfg in PAPER_CNNS.items():
        net = build_simnet(cfg)
        st = single_thread_latency(net)
        ws = simulate(net, policy="ws", frames=FRAMES)
        flops = cnn_flops_per_frame(cfg)
        e_orig = P_CPU_W * st * 1e3                  # mJ/frame
        e_syn = P_SYNERGY_W / ws.fps * 1e3
        rows.append({"net": name, "orig_mj": e_orig, "synergy_mj": e_syn,
                     "reduction": 1 - e_syn / e_orig,
                     "orig_gops_w": flops / st / P_CPU_W / 1e9,
                     "syn_gops_w": flops * ws.fps / P_SYNERGY_W / 1e9,
                     "fps": ws.fps})
    mean_red = statistics.mean(r["reduction"] for r in rows)
    return rows, {"mean_energy_reduction": mean_red, "paper": 0.8013}


def runtime_steal():
    """Live-runtime mirror of the Fig 13 / Table 6 claim: a steady-frame
    ThreadedPipeline through runtime_scope() on >=2 simulated PEs shows
    nonzero steals and a higher aggregate busy fraction than the same
    workload pinned single-engine (the acceptance metric of the runtime
    PR, on REAL threads instead of the DES)."""
    import jax
    import jax.numpy as jnp

    from repro.core.pipeline import EngineStage, ThreadedPipeline
    from repro.engines import get_engine
    from repro.soc import SynergyRuntime

    pool = ["F-PE", "S-PE"]
    engines = [get_engine(n) for n in pool]
    w = jax.random.normal(jax.random.key(0), (64, 48))
    frames = [jax.random.normal(jax.random.key(i), (320, 64))
              for i in range(8)]
    stages = [EngineStage.gemm("mm", w, engine="F-PE", tile=(32, 32, 32)),
              ("post", lambda y: float(jnp.sum(y)))]

    def busy_frac(before, after):
        d = [a.busy_s - b.busy_s for b, a in zip(before, after)]
        return sum(d) / (len(d) * max(d)) if max(d) > 0 else 0.0

    snap = lambda: [e.telemetry.snapshot() for e in engines]
    b0 = snap()
    _, pinned = ThreadedPipeline(stages).run(frames)
    pinned_frac = busy_frac(b0, snap())
    with SynergyRuntime(pool, name="bench") as rt, rt.scope():
        b1 = snap()
        _, st = ThreadedPipeline(stages).run(frames)
        rt_frac = busy_frac(b1, snap())
    rstats = st["runtime"]
    rows = [{"mode": "pinned(F-PE)", "fps": pinned["fps"],
             "busy_fraction": pinned_frac, "steals": 0},
            {"mode": "runtime(F-PE+S-PE)", "fps": st["fps"],
             "busy_fraction": rt_frac,
             "steals": rstats["total_steals"],
             "per_engine": {k: v["jobs"]
                            for k, v in rstats["engines"].items()}}]
    return rows, {
        "steals": rstats["total_steals"],
        "busy_fraction_pinned": round(pinned_frac, 3),
        "busy_fraction_runtime": round(rt_frac, 3),
        "runtime_beats_pinned": rt_frac > pinned_frac,
    }


def quant_pool():
    """Heterogeneous precision zoo (ISSUE 3): a mixed fp32+int8+VPU pool
    must beat the BEST homogeneous (single-precision-class) pool on
    busy-fraction-weighted simulated fps, with the int8 engine's decode
    outputs inside its calibrated tolerance of the fp32 oracle.

    The pool is one chip's worth of engines: the full-precision tile PE,
    its int8 weight-only twin (4x calibrated rate — weight bandwidth), and
    the VPU/NEON vector engine at the paper's 0.42x F-PE calibration.
    Virtual-time SimRuntime (the DES-conformant twin) supplies makespans,
    so the numbers are cost-model truth, not host-machine noise."""
    import jax

    from repro.core.job import JobSet
    from repro.engines.sim import SIM_ENGINE_SPECS, SimPEEngine
    from repro.engines.vpu import NeonVpuEngine
    from repro.quant import QuantizedEngine, calibrate, rel_err
    from repro.soc import SimRuntime

    fp32 = SimPEEngine("zoo-fp32", SIM_ENGINE_SPECS["F-PE"])
    int8 = QuantizedEngine(fp32, name="zoo-int8")
    vpu = NeonVpuEngine("zoo-vpu", interpret=True,
                        cost=SIM_ENGINE_SPECS["NEON"])
    report = calibrate(int8, tol=0.05)

    n_frames = min(FRAMES, 16)
    frames = [JobSet.for_gemm(i, 128, 256, 64, 32, name=f"decode{i}")
              for i in range(n_frames)]

    def run_pool(engines):
        makespan, fracs = 0.0, []
        for js in frames:
            res = SimRuntime(engines).run(js)
            makespan += res.makespan_s
            fracs.append(res.aggregate_busy_fraction)
        fps = len(frames) / makespan
        frac = statistics.mean(fracs)
        return {"fps": fps, "busy_fraction": frac,
                "weighted_fps": fps * frac}

    pools = {"fp32-only": [fp32], "int8-only": [int8], "vpu-only": [vpu],
             "mixed": [fp32, int8, vpu]}
    rows = [{"pool": name, **run_pool(engines)}
            for name, engines in pools.items()]
    by_name = {r["pool"]: r for r in rows}
    best_homog = max((r for r in rows if r["pool"] != "mixed"),
                     key=lambda r: r["weighted_fps"])

    # decode-accuracy leg: one real decode GEMM through the int8 engine,
    # measured with the SAME formula the calibration gate uses
    ka, kb = jax.random.split(jax.random.key(0))
    a = jax.random.normal(ka, (4, 64))
    w = jax.random.normal(kb, (64, 256)) * 0.05
    rel = rel_err(int8.execute(a, w), fp32.execute(a, w))

    return rows, {
        "mixed_vs_best_homogeneous":
            by_name["mixed"]["weighted_fps"] / best_homog["weighted_fps"],
        "best_homogeneous": best_homog["pool"],
        "mixed_wins":
            by_name["mixed"]["weighted_fps"] > best_homog["weighted_fps"],
        "int8_decode_rel_err": rel,
        "int8_tol": report.tol,
        "int8_within_tol": rel <= report.tol,
    }


def qmm_int8x8():
    """ISSUE 4 acceptance: the TRUE int8×int8 path vs the weight-only
    fp32-cast dot it replaces, on wall time and rel-error.

    Two legs, each over decode-shaped GEMMs (k = d_model, n = 4*d_model
    of the reduced serving configs; m = 1 single-token plus small decode
    batches):

      * **pallas-pe** (the headline): the quantized tile PE vs the fp32
        tile PE under the SAME executor — the qmm kernel against
        ``tiled_mm`` over fp32-cast weights followed by the unfused
        dequant tail (the weight-only PE cannot fuse the full-width (n,)
        scale — that separate pass is part of its real cost).  Off-TPU
        both kernels run through the Pallas interpreter; on TPU both run
        native (Mosaic), where the int8 MXU mode is the whole point.
      * **xla-dot**: the same two paths on the raw XLA backend.  Honest
        caveat, visible in the rows: XLA *CPU* ships no vectorized int8
        GEMM, so off-TPU this leg hovers near parity — the bandwidth win
        the kernel is built for needs hardware with an int8 datapath.

    The derived block also reports the measured int8 MAC rate — the
    number that replaces the simulated 4x in the QuantizedEngine cost
    model (``register_quantized`` / runtime recalibration persist it)."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.kernels.qmm import qmm_matmul
    from repro.kernels.tiled_mm import tiled_matmul
    from repro.quant import (DEFAULT_TOL, dequant_finish, quant_gemm,
                             quantize_weights, rel_err)
    from repro.quant.act import one_shot_act_scale, quantize_activations

    smoke = FRAMES < 96
    rounds = 3 if smoke else 7
    interpret = jax.default_backend() != "tpu"
    shapes = [(1, 256, 1024), (8, 256, 1024), (32, 256, 512)]
    rows = []
    for m, k, n in shapes:
        ka, kb = jax.random.split(jax.random.key(0))
        a = jax.random.normal(ka, (m, k))
        w = jax.random.normal(kb, (k, n)) * 0.05
        qw = quantize_weights(w)
        act_scale = one_shot_act_scale(a)
        a_q = quantize_activations(a, act_scale)
        ref = jnp.dot(a, w)

        def pe_weight_only(a=a, qw=qw):
            acc = tiled_matmul(a, qw.q.astype(jnp.float32), tile=32,
                               interpret=interpret, out_dtype=jnp.float32)
            return dequant_finish(acc, qw, out_dtype=jnp.float32)

        def pe_int8x8(a_q=a_q, qw=qw, s=act_scale):
            return qmm_matmul(a_q, qw.q, qw.scale, act_scale=s,
                              tile=(32, 32, 32), interpret=interpret)

        xla_weight_only = jax.jit(lambda a, qw=qw: quant_gemm(a, qw))
        xla_int8x8 = jax.jit(lambda a, qw=qw, s=act_scale:
                             quant_gemm(a, qw, act_scale=s))

        def median_wall(fn, *args):
            jax.block_until_ready(fn(*args))      # compile outside timing
            walls = []
            for _ in range(rounds):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                walls.append(time.perf_counter() - t0)
            return statistics.median(walls)

        for leg, fn_wo, fn_q8, out_q8 in (
                ("pallas-pe", median_wall(pe_weight_only),
                 median_wall(pe_int8x8), pe_int8x8()),
                ("xla-dot", median_wall(xla_weight_only, a),
                 median_wall(xla_int8x8, a), xla_int8x8(a))):
            rows.append({
                "leg": f"{leg} {m}x{k}x{n}",
                "fp32_dot_us": fn_wo * 1e6,
                "int8x8_us": fn_q8 * 1e6,
                "speedup": fn_wo / fn_q8,
                "rel_err_int8x8": rel_err(out_q8, ref),
                "int8_macs_per_s": m * k * n / fn_q8,
            })
    pe = [r for r in rows if r["leg"].startswith("pallas-pe")]
    xla = [r for r in rows if r["leg"].startswith("xla-dot")]
    max_rel = max(r["rel_err_int8x8"] for r in rows)
    return rows, {
        # headline: the quantized tile PE vs the fp32 tile PE
        "pe_int8_speedup_median": statistics.median(
            [r["speedup"] for r in pe]),
        "int8_beats_fp32_dot": all(r["speedup"] > 1.0 for r in pe),
        "xla_dot_int8_speedup_median": statistics.median(
            [r["speedup"] for r in xla]),
        "max_rel_err": max_rel,
        "tol": DEFAULT_TOL,
        "within_tol": max_rel <= DEFAULT_TOL,
        "measured_int8_macs_per_s": statistics.median(
            [r["int8_macs_per_s"] for r in xla]),
    }


def serve_throughput():
    """ISSUE 5 acceptance: continuous batching + async in-flight
    submissions vs the legacy one-request-per-step server.

    Both servers run the SAME workload through a live SynergyRuntime over
    the paper's calibrated F-PE/S-PE/NEON sim engines: real conv-as-GEMM
    prefill (batched im2col), real coalesced decode GEMM submissions.
    The BASELINE admits one request per step, submits one decode GEMM per
    live slot, and reaps synchronously (``max_inflight=0``); the batched
    mode admits a full wave per step, coalesces the live slots into one
    submission, and overlaps an in-flight window of 4.

    Metrics: wall tokens/s and requests/s per mode (machine-dependent —
    reported but NOT gated), and ``tokens_per_s_rel`` — each mode's
    tokens/s relative to the per-request baseline of the SAME run, the
    machine-stable ratio ``check_regression.py`` gates (>20% drop fails).
    The conv front-end is a reduced MNIST-topology net so host compute
    does not swamp the dispatch-overhead signal the benchmark measures —
    the same reduced-config convention every serving test uses."""
    import time

    import jax

    from repro.configs import ARCHS, reduced
    from repro.core.serving import Request, SynergyServer
    from repro.models import init_model
    from repro.models.cnn import CNNConfig
    from repro.soc import SynergyRuntime

    cfg = reduced(ARCHS["granite-3-2b"], n_layers=2, d_model=32,
                  n_heads=2, d_ff=64, vocab=128)
    params = init_model(cfg, jax.random.key(0))
    # serving-scale tile (256 rows/panel): the batched wave's 4096-row
    # conv GEMM splits into a handful of full panels, while the
    # per-request baseline pays per-chain panel rounding — exactly the
    # dispatch amortization the row-panel split exists for (the paper's
    # TS=32 stays the default elsewhere; tile choice is a serving knob)
    tiny_cnn = CNNConfig(
        name="MNIST-r8", input_hw=8, cin=1, tile=256, layers=(
            ("conv", 8, 3, 1, 1), ("pool", 2),
            ("conv", 16, 3, 1, 1), ("pool", 2), ("fc", 10)))
    # the workload is deliberately NOT shrunk under --smoke: the gated
    # tokens_per_s_rel ratio must come from the same request mix in the
    # committed baseline and the CI smoke run (the whole benchmark is a
    # few seconds), or the gate would compare different workloads
    n_req, reps = 24, 3
    slots, new_tokens, plen = 8, 8, 8

    def requests(base):
        return [Request(base + i,
                        jax.random.randint(jax.random.key(i), (plen,), 0,
                                           128),
                        max_new_tokens=new_tokens) for i in range(n_req)]

    def make_server(rt, admission, decode_mode, max_inflight):
        srv = SynergyServer(cfg, params, slots=slots, max_len=32,
                            prefill_len=plen, runtime=rt,
                            prefill_cnn=tiny_cnn, admission=admission,
                            decode_mode=decode_mode,
                            max_inflight=max_inflight)
        for r in requests(0):              # warmup: jit compiles
            srv.submit(r)
        srv.run()
        return srv

    def measure(srv, rep):
        srv.reset_stats()
        for r in requests((rep + 1) * 1000):
            srv.submit(r)
        t0 = time.perf_counter()
        stats = srv.run()
        dt = time.perf_counter() - t0
        return stats.tokens_out / dt, stats.prefills / dt, stats

    # the two modes are measured back-to-back INSIDE each repetition and
    # compared as per-rep ratios: host drift (compile threads, cgroup
    # neighbors) hits both legs of a rep alike, so the median ratio is
    # far more stable than a ratio of independently-measured medians
    with SynergyRuntime(["F-PE", "S-PE", "NEON"], name="serve-base") as rt0, \
            SynergyRuntime(["F-PE", "S-PE", "NEON"],
                           name="serve-batched") as rt1:
        base_srv = make_server(rt0, "single", "per-slot", 0)
        bat_srv = make_server(rt1, "wave", "batched", 4)
        base_samples, bat_samples, ratios = [], [], []
        for rep in range(reps):
            b_tok, b_req, base_stats = measure(base_srv, rep)
            a_tok, a_req, bat_stats = measure(bat_srv, rep)
            base_samples.append((b_tok, b_req))
            bat_samples.append((a_tok, a_req))
            ratios.append(a_tok / b_tok)
    med = lambda xs: statistics.median(xs)   # per-field, not paired-tuple
    base_tok, base_req = (med([s[0] for s in base_samples]),
                          med([s[1] for s in base_samples]))
    bat_tok, bat_req = (med([s[0] for s in bat_samples]),
                        med([s[1] for s in bat_samples]))
    speedup = statistics.median(ratios)
    rows = [
        {"mode": "per-request", "tokens_per_s_wall": base_tok,
         "requests_per_s_wall": base_req, "tokens_per_s_rel": 1.0,
         "prefill_waves": base_stats.prefill_waves,
         "runtime_jobs": base_stats.runtime_jobs,
         "inflight_peak": base_stats.inflight_peak},
        {"mode": "batched-async", "tokens_per_s_wall": bat_tok,
         "requests_per_s_wall": bat_req,
         "tokens_per_s_rel": speedup,
         "prefill_waves": bat_stats.prefill_waves,
         "runtime_jobs": bat_stats.runtime_jobs,
         "inflight_peak": bat_stats.inflight_peak},
    ]
    return rows, {
        "batched_speedup_tokens_per_s": speedup,
        "batched_speedup_requests_per_s": bat_req / base_req,
        "meets_2x": speedup >= 2.0,
        "baseline_tokens_per_s": base_tok,
        "batched_tokens_per_s": bat_tok,
        "prefill_waves": {"per-request": base_stats.prefill_waves,
                          "batched": bat_stats.prefill_waves},
    }


def graph_overlap():
    """ISSUE 6 acceptance: dataflow-graph prefill vs the serialized
    conv-chain, plus chunked prefill's decode tail latency.

    Leg 1 (``prefill_overlap_rel``, gated >= 1.3x): the same conv
    prefill waves on a 2-engine pool, (a) as the PR-5-style CHAIN —
    gather, submit the layer GEMM, block on its result, gather the next
    layer, one wave at a time — vs (b) as ``submit_graph`` DAGs, all
    waves in flight at once: layer l+1's im2col gather runs on the host
    executor WHILE layer l's panels execute on the workers, and
    independent waves fill both engines.  The pool uses PACED engines
    whose ``execute`` sleeps out the MAC-rate cost model before the real
    math — the wall-clock analog of the DES PE timing (``time.sleep``
    releases the GIL, so measured overlap is genuine engine-busy
    overlap, not Python scheduling noise).  Each wave's conv GEMMs are
    single row panels (m <= tile), the regime the paper's dataflow
    pipelining targets: one layer alone cannot fill the pool, only
    cross-wave/cross-layer concurrency can.  Measured back-to-back
    inside each repetition; the gated number is the median per-rep fps
    ratio.

    Leg 2 (``decode_p99_rel``, gated): one request trace through
    ``SynergyServer`` with blocking admission vs ``prefill_chunk_macs``
    chunking, recording the wall-clock gap between consecutive decode
    advances.  Blocking admission stalls live decoders for a whole wave
    (conv graph + full LM replay) — its p99 inter-decode gap balloons;
    chunked prefill bounds it.  The gated ratio is
    ``p99_blocking / p99_chunked`` (> 1 means chunking improves the
    decode tail), medianed over repetitions."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.configs import ARCHS, reduced
    from repro.core.im2col import im2col_wave
    from repro.core.serving import Request, SynergyServer
    from repro.engines import CAP_GEMM, CostModel, Engine
    from repro.models import init_model
    from repro.models.cnn import (CNNConfig, conv_graph_steps, conv_jobsets,
                                  conv_wave_graph, init_cnn, maxpool2d)
    from repro.soc import SynergyRuntime

    class _PacedEngine(Engine):
        """Sleeps out the cost model's busy time, then runs the real
        math — an F-PE whose MAC rate is enforced on the wall clock."""

        def __init__(self, name, macs_per_s):
            super().__init__(name, {CAP_GEMM, "epilogue"},
                             cost=CostModel(macs_per_s=macs_per_s))
            self._macs_per_s = macs_per_s

        def execute(self, a, b, *, bias=None, activation=None, tile=None,
                    out_dtype=None, precision=None):
            m, k = a.shape
            time.sleep(m * k * b.shape[1] / self._macs_per_s)
            y = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
            if bias is not None:
                y = y + bias
            if activation is not None:
                y = activation(y)
            return y.astype(out_dtype or a.dtype)

    cnn = CNNConfig(
        name="MNIST-r8", input_hw=8, cin=1, tile=256, layers=(
            ("conv", 8, 3, 1, 1), ("pool", 2),
            ("conv", 16, 3, 1, 1), ("pool", 2), ("fc", 10)))
    cnn_params = init_cnn(cnn, jax.random.key(0))
    steps = conv_graph_steps(cnn)
    # like serve_throughput, the workload is NOT shrunk under --smoke:
    # the gated ratios must come from the same work mix as the baseline.
    # n_frames=4 keeps every conv GEMM a single <=256-row panel.
    n_frames, waves, reps = 4, 8, 5
    pace_macs_per_s = 4e6
    frames = [jax.random.normal(jax.random.key(100 + w),
                                (n_frames, cnn.input_hw, cnn.input_hw,
                                 cnn.cin)) for w in range(waves)]

    def wave_jobsets(w):
        return [js for _, js in
                conv_jobsets(cnn, n_frames, name_prefix=f"w{w}/")]

    def run_chain(rt):
        t0 = time.perf_counter()
        for w in range(waves):
            x = frames[w]
            for (i, pools, (k, s, p), (oh, ow, cout)), js in zip(
                    steps, wave_jobsets(w)):
                for size in pools:
                    x = maxpool2d(x, size)
                a = im2col_wave(x, k, k, s, p)
                y = rt.submit_gemm(
                    a, cnn_params[f"conv{i}_w"].reshape(-1, cout),
                    jobset=js, bias=cnn_params[f"conv{i}_b"],
                    activation=jax.nn.relu, tile=(js.ts_m, js.ts_n, js.ts_k),
                    job_class="prefill").result(240)
                x = y.reshape(n_frames, oh, ow, cout)
        return waves * n_frames / (time.perf_counter() - t0)

    def run_dag(rt):
        t0 = time.perf_counter()
        futs = []
        for w in range(waves):
            nodes, edges = conv_wave_graph(cnn, cnn_params, frames[w],
                                           steps, wave_jobsets(w), n_frames)
            futs.append(rt.submit_graph(nodes, edges, name=f"wave{w}"))
        for gf in futs:
            gf.result(240)
        return waves * n_frames / (time.perf_counter() - t0)

    def paced_pool():
        return [_PacedEngine("paced-a", pace_macs_per_s),
                _PacedEngine("paced-b", pace_macs_per_s)]

    with SynergyRuntime(paced_pool(), name="ovl-chain") as rt_c, \
            SynergyRuntime(paced_pool(), name="ovl-graph") as rt_g:
        run_chain(rt_c)                     # warmup: jit compiles
        run_dag(rt_g)
        chain_fps, graph_fps, ratios = [], [], []
        for _ in range(reps):
            c = run_chain(rt_c)
            g = run_dag(rt_g)
            chain_fps.append(c)
            graph_fps.append(g)
            ratios.append(g / c)
    overlap_rel = statistics.median(ratios)

    # ---- leg 2: decode tail latency under concurrent prefill ----------
    cfg = reduced(ARCHS["granite-3-2b"], n_layers=2, d_model=32,
                  n_heads=2, d_ff=64, vocab=128)
    params = init_model(cfg, jax.random.key(0))
    # plen=32: the blocking wave's synchronous LM replay (32 tokens in
    # one admission) towers over a single decode step, which is what
    # chunking amortizes; n_req=32 gives enough decode gaps per rep for
    # a stable p99
    n_req, slots, plen = 32, 4, 32

    def requests(base):
        # staggered lengths: slots free at DIFFERENT times, so blocking
        # wave admission lands while other decoders are still live
        return [Request(base + i,
                        jax.random.randint(jax.random.key(i), (plen,), 0,
                                           128),
                        max_new_tokens=4 + (i % 9)) for i in range(n_req)]

    def make_server(rt, chunk):
        srv = SynergyServer(cfg, params, slots=slots, max_len=64,
                            prefill_len=plen, runtime=rt, prefill_cnn=cnn,
                            max_inflight=4, prefill_chunk_macs=chunk)
        for r in requests(0):              # warmup: jit compiles
            srv.submit(r)
        srv.run()
        return srv

    def p99_decode_gap(srv, rep):
        stamps = []
        orig = srv._do_decode

        def timed():
            orig()
            stamps.append(time.perf_counter())

        srv._do_decode = timed
        try:
            srv.reset_stats()
            for r in requests((rep + 1) * 1000):
                srv.submit(r)
            stats = srv.run()
        finally:
            srv._do_decode = orig
        gaps = sorted(b - a for a, b in zip(stamps, stamps[1:]))
        return gaps[int(0.99 * (len(gaps) - 1))], stats

    # ~1-token LM-replay quanta + one conv jobset per chunk at this cfg
    chunk_macs = 16_384
    with SynergyRuntime(["F-PE", "S-PE"], name="p99-blk") as rt_b, \
            SynergyRuntime(["F-PE", "S-PE"], name="p99-chk") as rt_k:
        blk_srv = make_server(rt_b, None)
        chk_srv = make_server(rt_k, chunk_macs)
        blk_p99s, chk_p99s, p99_ratios = [], [], []
        for rep in range(reps):
            b99, blk_stats = p99_decode_gap(blk_srv, rep)
            c99, chk_stats = p99_decode_gap(chk_srv, rep)
            blk_p99s.append(b99)
            chk_p99s.append(c99)
            p99_ratios.append(b99 / c99)
    p99_rel = statistics.median(p99_ratios)

    rows = [
        {"mode": "conv-chain", "fps_wall": statistics.median(chain_fps),
         "prefill_overlap_rel": 1.0},
        {"mode": "graph", "fps_wall": statistics.median(graph_fps),
         "prefill_overlap_rel": overlap_rel},
        {"mode": "blocking-admission",
         "decode_p99_gap_s_wall": statistics.median(blk_p99s),
         "decode_stall_steps": blk_stats.decode_stall_steps,
         "decode_p99_rel": 1.0},
        {"mode": "chunked-prefill",
         "decode_p99_gap_s_wall": statistics.median(chk_p99s),
         "decode_stall_steps": chk_stats.decode_stall_steps,
         "prefill_chunks": chk_stats.prefill_chunks,
         "prefill_chunk_macs": chunk_macs,
         "decode_p99_rel": p99_rel},
    ]
    return rows, {
        "prefill_overlap_rel": overlap_rel,
        "meets_1_3x": overlap_rel >= 1.3,
        "chain_fps_wall": statistics.median(chain_fps),
        "graph_fps_wall": statistics.median(graph_fps),
        "decode_p99_rel": p99_rel,
        "chunked_improves_p99": p99_rel > 1.0,
        "blocking_decode_stall_steps": blk_stats.decode_stall_steps,
        "chunked_decode_stall_steps": chk_stats.decode_stall_steps,
    }


def qos_slo():
    """ISSUE 7 acceptance: multi-tenant QoS under overload, plus
    self-healing pool recovery.

    Leg 1 (``slo_attainment_rel``, gated >= 1.5x): an overloaded
    2-tenant request mix — a BULK flood submitted ahead of a small GOLD
    (interactive, deadlined) stream — served on a PACED 2-engine pool,
    (a) by the untenanted FIFO server and (b) by the QoS server (gold:
    priority 10, weight 4, tenant-class deadline; bulk: sheddable, no
    deadline).  FIFO admits in arrival order, so every gold request
    waits behind the whole flood; QoS admission picks gold first and its
    prefill/decode panels carry priority tags through the runtime.  The
    gold deadline is SELF-CALIBRATED each run (2.5x the measured solo
    gold makespan on the same warmed pool, +0.25 s timer floor), so the
    attainment gap measures scheduling policy, not host speed.  The
    gated number is the median per-rep ratio of gold deadline
    attainment, with the FIFO denominator floored at one hit so a
    total-miss baseline cannot divide by zero.

    Leg 2 (``recovery_fps_rel``, gated >= 0.8): a heterogeneous paced
    pool (two fast engines + one slow at 1/4 rate) runs GEMM waves
    (a) healthy, (b) with the slow engine GRINDING at 12x its calibrated
    cost (health checks off — stragglers gate every wave), and (c) with
    the self-healing policy on: the runtime notices the rate collapse,
    quarantines the sick engine, drains its queue to the survivors, and
    steady-state throughput is measured AFTER the quarantine event.  The
    sick engine contributes 1/9 of pool capacity, so full recovery is
    ~0.89x healthy fps — gated at >= 0.8; the grinding fps is reported
    alongside to show what quarantine buys.

    Like serve_throughput/graph_overlap, the workload is NOT shrunk
    under --smoke: the gated ratios must come from the same work mix as
    the committed baseline."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.configs import ARCHS, reduced
    from repro.core.job import JobSet
    from repro.core.serving import Request, SynergyServer
    from repro.engines import CAP_GEMM, CostModel, Engine
    from repro.models import init_model
    from repro.models.cnn import CNNConfig
    from repro.soc import HealthPolicy, SynergyRuntime, Tenant
    from repro.soc.qos import QosClass

    class _PacedEngine(Engine):
        """Sleeps out the cost model's busy time (x a mutable grind
        factor), then runs the real math."""

        def __init__(self, name, macs_per_s):
            super().__init__(name, {CAP_GEMM, "epilogue"},
                             cost=CostModel(macs_per_s=macs_per_s))
            self._macs_per_s = macs_per_s
            self.grind = 1.0          # >1: engine is sick

        def execute(self, a, b, *, bias=None, activation=None, tile=None,
                    out_dtype=None, precision=None):
            m, k = a.shape
            time.sleep(m * k * b.shape[1] / self._macs_per_s * self.grind)
            y = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
            if bias is not None:
                y = y + bias
            if activation is not None:
                y = activation(y)
            return y.astype(out_dtype or a.dtype)

    # ---- leg 1: FIFO vs QoS gold deadline attainment ------------------
    cnn = CNNConfig(
        name="MNIST-r8", input_hw=8, cin=1, tile=256, layers=(
            ("conv", 8, 3, 1, 1), ("pool", 2),
            ("conv", 16, 3, 1, 1), ("pool", 2), ("fc", 10)))
    cfg = reduced(ARCHS["granite-3-2b"], n_layers=2, d_model=32,
                  n_heads=2, d_ff=64, vocab=128)
    params = init_model(cfg, jax.random.key(0))
    n_gold, n_bulk, slots, plen, reps = 4, 12, 2, 8, 3
    pace = 1e6                      # paced time dominates host overhead

    def pool2():
        return [_PacedEngine("slo-a", pace), _PacedEngine("slo-b", pace)]

    def requests(base, n, tenant, max_new, deadline_s=None):
        return [Request(base + i,
                        jax.random.randint(jax.random.key(base + i),
                                           (plen,), 0, 128),
                        max_new_tokens=max_new, tenant=tenant,
                        deadline_s=deadline_s) for i in range(n)]

    def make_server(rt, tenants):
        srv = SynergyServer(cfg, params, slots=slots, max_len=32,
                            prefill_len=plen, runtime=rt, prefill_cnn=cnn,
                            tenants=tenants)
        warm = "gold" if tenants else None
        for r in requests(900_000, slots, warm, 2):   # warmup: jit
            srv.submit(r)
        srv.run()
        srv.reset_stats()
        return srv

    gold_attains = {"fifo": [], "qos": []}
    ratios = []
    with SynergyRuntime(pool2(), name="slo-fifo") as rt_f, \
            SynergyRuntime(pool2(), name="slo-qos") as rt_q:
        # gold tenant first: the calibration run needs it to exist
        gold_cls = QosClass("gold", priority=10, deadline_s=None,
                            weight=4.0)
        bulk_cls = QosClass("bulk", priority=-10, sheddable=True)
        qos_srv = make_server(rt_q, [Tenant("gold", gold_cls),
                                     Tenant("bulk", bulk_cls)])
        fifo_srv = make_server(rt_f, None)
        # self-calibrate the deadline: solo gold makespan on this host
        t0 = time.perf_counter()
        for r in requests(800_000, n_gold, "gold", 4):
            qos_srv.submit(r)
        qos_srv.run()
        solo_s = time.perf_counter() - t0
        deadline_s = 2.5 * solo_s + 0.25
        qos_srv.reset_stats()

        for rep in range(reps):
            base = (rep + 1) * 10_000
            # FIFO: bulk flood first, gold behind it, arrival order wins
            bulk_f = requests(base, n_bulk, None, 8)
            gold_f = requests(base + 5000, n_gold, None, 4,
                              deadline_s=deadline_s)
            for r in bulk_f + gold_f:
                fifo_srv.submit(r)
            fifo_srv.run()
            fifo_hits = sum(1 for r in gold_f if r.done_at is not None
                            and r.done_at <= r.deadline_at)
            # QoS: same arrival order; priority admission + tagged panels
            bulk_q = requests(base, n_bulk, "bulk", 8)
            gold_q = requests(base + 5000, n_gold, "gold", 4,
                              deadline_s=deadline_s)
            for r in bulk_q + gold_q:
                qos_srv.submit(r)
            qstats = qos_srv.run()
            qos_hits = qstats.tenants["gold"].deadline_hits
            qos_srv.reset_stats()
            gold_attains["fifo"].append(fifo_hits / n_gold)
            gold_attains["qos"].append(qos_hits / n_gold)
            ratios.append(qos_hits / max(fifo_hits, 1))
    slo_rel = statistics.median(ratios)

    # ---- leg 2: self-healing pool recovery ----------------------------
    fast, waves_t = 4e6, 16

    def pool3():
        return [_PacedEngine("heal-a", fast), _PacedEngine("heal-b", fast),
                _PacedEngine("heal-c", fast / 4)]

    def run_wave(rt, step):
        a = jnp.ones((128, 32)); b = jnp.ones((32, 32))
        futs = [rt.submit_gemm(
            a, b, jobset=JobSet.for_gemm(step * 3 + i, 128, 32, 32, 32,
                                         name=f"hw{step}/{i}"),
            tile=(32, 32, 32)) for i in range(3)]
        for f in futs:
            f.result(240)

    def timed_waves(rt, base, n=waves_t):
        t0 = time.perf_counter()
        for s in range(n):
            run_wave(rt, base + s)
        return n / (time.perf_counter() - t0)

    # probes disabled: the engine stays sick, readmission would only
    # re-introduce the straggler into the timed window
    heal = HealthPolicy(alpha=0.5, quarantine_below=0.5,
                        readmit_above=0.8, min_samples=3,
                        probe_interval_s=1e9, min_probe_samples=2)
    with SynergyRuntime(pool3(), name="heal-base") as rt:
        run_wave(rt, 990)                      # warmup: jit compiles
        healthy_fps = timed_waves(rt, 0)
    with SynergyRuntime(pool3(), name="heal-grind") as rt:
        rt.find_engine("heal-c").grind = 12.0
        grind_fps = timed_waves(rt, 100, n=6)
    with SynergyRuntime(pool3(), name="heal-heal", health=heal) as rt:
        for s in range(4):          # healthy EMA baseline, then degrade
            run_wave(rt, 190 + s)
        rt.find_engine("heal-c").grind = 12.0
        quarantined_after = None
        for s in range(40):                    # detection phase, untimed
            run_wave(rt, 200 + s)
            if rt.stats()["quarantines"] >= 1:
                quarantined_after = s + 1
                break
        recovered_fps = timed_waves(rt, 300)
    recovery_rel = recovered_fps / healthy_fps
    grind_rel = grind_fps / healthy_fps

    rows = [
        {"mode": "fifo", "gold_attainment": statistics.median(
            gold_attains["fifo"]), "slo_attainment_rel": 1.0},
        {"mode": "qos", "gold_attainment": statistics.median(
            gold_attains["qos"]), "gold_deadline_s": deadline_s,
         "slo_attainment_rel": slo_rel},
        {"mode": "pool-healthy", "fps_wall": healthy_fps,
         "recovery_fps_rel": 1.0},
        {"mode": "pool-grinding", "fps_wall": grind_fps,
         "grind_fps_rel": grind_rel},
        {"mode": "pool-quarantined", "fps_wall": recovered_fps,
         "quarantined_after_waves": quarantined_after,
         "recovery_fps_rel": recovery_rel},
    ]
    return rows, {
        "slo_attainment_rel": slo_rel,
        "meets_1_5x": slo_rel >= 1.5,
        "gold_deadline_s": deadline_s,
        "fifo_gold_attainment": statistics.median(gold_attains["fifo"]),
        "qos_gold_attainment": statistics.median(gold_attains["qos"]),
        "recovery_fps_rel": recovery_rel,
        "meets_0_8x_recovery": recovery_rel >= 0.8,
        "grind_fps_rel": grind_rel,
        "quarantined_after_waves": quarantined_after,
    }


def obs_overhead():
    """ISSUE 8 gate: the span tracer must cost <= 5% on the paced pool.

    The same GEMM-wave workload runs on a 3-engine PACED pool twice per
    rep — tracer off (the default no-tracer runtime: every emit site is
    one attribute check) and tracer on (a 1M-event ring recording seed /
    enqueue / dequeue / panel / steal events for every wave) — and the
    gated number is the median per-rep fps ratio ``traced / untraced``
    (``trace_overhead_rel``, floored at 0.95 in check_regression.py).
    Panels sleep out cost-model time like graph_overlap/qos_slo, so the
    ratio is machine-stable: the tracer's per-event cost is measured
    against realistic panel durations, not against a trivially fast
    in-cache GEMM.  Not shrunk under --smoke for the same reason as the
    other gated benchmarks."""
    import statistics
    import time

    import jax.numpy as jnp

    from repro.core.job import JobSet
    from repro.engines import CAP_GEMM, CostModel, Engine
    from repro.obs.trace import Tracer, trace_scope
    from repro.soc import SynergyRuntime

    pace = 4e6
    waves, reps = 8, 3

    class _PacedEngine(Engine):
        def __init__(self, name):
            super().__init__(name, {CAP_GEMM, "epilogue"},
                             cost=CostModel(macs_per_s=pace))

        def execute(self, a, b, *, bias=None, activation=None, tile=None,
                    out_dtype=None, precision=None):
            m, k = a.shape
            time.sleep(m * k * b.shape[1] / pace)
            y = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
            return y.astype(out_dtype or a.dtype)

    def pool():
        return [_PacedEngine("obs-a"), _PacedEngine("obs-b"),
                _PacedEngine("obs-c")]

    def run_wave(rt, step):
        a = jnp.ones((128, 32)); b = jnp.ones((32, 32))
        futs = [rt.submit_gemm(
            a, b, jobset=JobSet.for_gemm(step * 3 + i, 128, 32, 32, 32,
                                         name=f"ow{step}/{i}"),
            tile=(32, 32, 32)) for i in range(3)]
        for f in futs:
            f.result(240)

    def timed_fps(tracer, base):
        # trace_scope pins the process-default tracer for the leg: the
        # off leg must stay untraced (runtime fallback AND the dispatch
        # emit site read the default) even under `run.py --trace`
        with trace_scope(tracer), \
             SynergyRuntime(pool(), name="obs-bench",
                            tracer=tracer) as rt:
            run_wave(rt, base + 990)           # warmup: jit compiles
            t0 = time.perf_counter()
            for s in range(waves):
                run_wave(rt, base + s)
            return waves / (time.perf_counter() - t0)

    ratios, off_fps, on_fps, n_events = [], [], [], 0
    for rep in range(reps):
        f_off = timed_fps(None, rep * 1000)
        tracer = Tracer(capacity=1_000_000)
        f_on = timed_fps(tracer, rep * 1000 + 500)
        n_events = len(tracer.events())
        off_fps.append(f_off)
        on_fps.append(f_on)
        ratios.append(f_on / f_off)
    rel = statistics.median(ratios)
    rows = [{"mode": "tracer-off", "fps_wall": statistics.median(off_fps)},
            {"mode": "tracer-on", "fps_wall": statistics.median(on_fps),
             "trace_overhead_rel": rel, "events_per_leg": n_events}]
    return rows, {"trace_overhead_rel": round(rel, 4),
                  "events_per_leg": n_events}


def fault_recovery():
    """ISSUE 9 gate: a pool that loses an engine mid-run must keep at
    least 0.8x its clean throughput once recovery settles.

    A 3-engine PACED pool (two at ``fast``, one at ``fast/4`` — combined
    capacity 9 units) runs the GEMM-wave workload twice: a clean leg
    (full pool, no faults) and a fault leg where a deterministic
    FaultPlan KILLS the slow engine's worker mid-panel.  The heartbeat
    monitor declares the worker dead, its queued + in-flight panels
    re-seed onto the two survivors (capacity 8 units), and the timed
    window measures the recovered pool: ``fault_recovery_rel`` =
    recovered / clean fps, ideally ~8/9 = 0.89, floored at 0.8 in
    check_regression.py.  The detection phase (death through re-seed) is
    untimed, mirroring qos_slo's quarantine leg — the gate protects the
    steady recovered state, not the one wave that ate the heartbeat
    timeout.  Not shrunk under --smoke like the other gated benchmarks."""
    import time

    import jax.numpy as jnp

    from repro.core.job import JobSet
    from repro.engines import CAP_GEMM, CostModel, Engine
    from repro.soc import (FaultPlan, FaultSpec, RetryPolicy,
                           SynergyRuntime, wrap_pool)

    fast, waves = 4e6, 16

    class _PacedEngine(Engine):
        def __init__(self, name, macs_per_s):
            super().__init__(name, {CAP_GEMM, "epilogue"},
                             cost=CostModel(macs_per_s=macs_per_s))
            self._macs_per_s = macs_per_s

        def execute(self, a, b, *, bias=None, activation=None, tile=None,
                    out_dtype=None, precision=None):
            m, k = a.shape
            time.sleep(m * k * b.shape[1] / self._macs_per_s)
            y = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
            return y.astype(out_dtype or a.dtype)

    def pool():
        return [_PacedEngine("fr-a", fast), _PacedEngine("fr-b", fast),
                _PacedEngine("fr-c", fast / 4)]

    def run_wave(rt, step):
        a = jnp.ones((128, 32)); b = jnp.ones((32, 32))
        futs = [rt.submit_gemm(
            a, b, jobset=JobSet.for_gemm(step * 3 + i, 128, 32, 32, 32,
                                         name=f"frw{step}/{i}"),
            tile=(32, 32, 32)) for i in range(3)]
        for f in futs:
            f.result(240)

    def timed_waves(rt, base, n=waves):
        t0 = time.perf_counter()
        for s in range(n):
            run_wave(rt, base + s)
        return n / (time.perf_counter() - t0)

    retry = RetryPolicy(max_attempts=4, heartbeat_timeout_s=0.1,
                        monitor_interval_s=0.02)
    with SynergyRuntime(pool(), name="fr-clean") as rt:
        run_wave(rt, 990)                      # warmup: jit compiles
        clean_fps = timed_waves(rt, 0)
    plan = FaultPlan((FaultSpec("fr-c", "die", at_call=2),), seed=9)
    with SynergyRuntime(wrap_pool(pool(), plan), name="fr-fault",
                        retry=retry) as rt:
        deadline = time.perf_counter() + 60    # detection phase, untimed
        while (rt.stats()["worker_deaths"] < 1
               and time.perf_counter() < deadline):
            run_wave(rt, 100 + rt.stats()["submissions"])
        st = rt.stats()
        recovered_fps = timed_waves(rt, 300)
        st_final = rt.stats()
    rel = recovered_fps / clean_fps
    rows = [
        {"mode": "clean-pool", "fps_wall": clean_fps,
         "fault_recovery_rel": 1.0},
        {"mode": "recovered-pool", "fps_wall": recovered_fps,
         "fault_recovery_rel": rel,
         "worker_deaths": st_final["worker_deaths"],
         "orphan_reseeds": st_final["orphan_reseeds"],
         "retries": st_final["retries"]},
    ]
    return rows, {
        "fault_recovery_rel": round(rel, 4),
        "meets_0_8x": rel >= 0.8,
        "worker_deaths": st_final["worker_deaths"],
        "orphan_reseeds": st_final["orphan_reseeds"],
        "retries": st_final["retries"],
        "waves_to_detect": st["submissions"] // 3,
        "injected": list(map(list, plan.injected)),
    }


def restart_recovery():
    """ISSUE 10 gate: a server restored from a crash must keep at least
    0.8x a clean durable server's steady-state throughput.

    Two durable servers over identical 2-engine PACED pools serve the
    same workload: a clean leg (batch 1 completes normally) and a crash
    leg (a deterministic CrashPlan kills the process mid-batch-1;
    ``SynergyServer.restore`` rebuilds it from the latest snapshot +
    journal-suffix replay into a FRESH pool and finishes batch 1).
    Then both servers run identical timed batches BACK-TO-BACK inside
    each repetition, and the gated ratio is the median per-rep
    restored/clean fps — host drift hits both legs of a rep alike, the
    same pairing discipline serve_throughput uses.  Both legs journal
    every token, so ``restart_recovery_rel`` isolates the cost of
    *having been restored* — leftover replay state, restored caches,
    re-learned rates — not the cost of durability itself.  The restore
    and the batch-1 remnant are untimed, mirroring fault_recovery's
    untimed detection phase: the gate protects the steady restored
    state.  Not shrunk under --smoke (the gated ratio must come from
    the same workload as the committed baseline)."""
    import tempfile
    import time

    import jax
    import jax.numpy as jnp

    from repro.configs import ARCHS, reduced
    from repro.core.serving import Request, SynergyServer
    from repro.engines import CAP_GEMM, CostModel, Engine
    from repro.models import init_model
    from repro.models.cnn import CNNConfig
    from repro.soc import (CrashPlan, Durability, SimulatedCrash,
                           SynergyRuntime)

    cfg = reduced(ARCHS["granite-3-2b"], n_layers=2, d_model=32,
                  n_heads=2, d_ff=64, vocab=128)
    params = init_model(cfg, jax.random.key(0))
    tiny_cnn = CNNConfig(
        name="MNIST-r8", input_hw=8, cin=1, tile=256, layers=(
            ("conv", 8, 3, 1, 1), ("pool", 2),
            ("conv", 16, 3, 1, 1), ("pool", 2), ("fc", 10)))
    pace = 2e8

    class _PacedEngine(Engine):
        def __init__(self, name, macs_per_s):
            super().__init__(name, {CAP_GEMM, "epilogue"},
                             cost=CostModel(macs_per_s=macs_per_s))
            self._macs_per_s = macs_per_s

        def execute(self, a, b, *, bias=None, activation=None, tile=None,
                    out_dtype=None, precision=None):
            m, k = a.shape
            time.sleep(m * k * b.shape[1] / self._macs_per_s)
            y = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
            return y.astype(out_dtype or a.dtype)

    def pool():
        return [_PacedEngine("rr-a", pace), _PacedEngine("rr-b", pace)]

    n_req, new_tokens, plen = 8, 8, 4
    kw = dict(slots=4, max_len=32, prefill_len=plen,
              prefill_cnn=tiny_cnn, max_inflight=1)

    def requests(base):
        return [Request(base + i,
                        jax.random.randint(jax.random.key(i), (plen,), 0,
                                           128),
                        max_new_tokens=new_tokens) for i in range(n_req)]

    def timed_batch(srv, base):
        tok0 = srv.stats.tokens_out
        for r in requests(base):
            srv.submit(r)
        t0 = time.perf_counter()
        srv.run()
        dt = time.perf_counter() - t0
        return (srv.stats.tokens_out - tok0) / dt

    reps = 5
    with tempfile.TemporaryDirectory() as dc, \
            tempfile.TemporaryDirectory() as dx:
        # crash leg prelude: die mid-batch-1, restore into a fresh pool
        # (untimed), finish the remnant (untimed)
        dur_x = Durability(dx, snapshot_every=6)
        with SynergyRuntime(pool(), name="rr-crash") as rt:
            srv = SynergyServer(cfg, params, runtime=rt, durable=dur_x,
                                crash_plan=CrashPlan(at_step=7), **kw)
            try:
                for r in requests(0):
                    srv.submit(r)
                srv.run()
                raise RuntimeError("crash plan never fired")
            except SimulatedCrash:
                pass
            srv._ck.wait()      # flush the async snapshot writer so the
            rt.shutdown()       # tempdir teardown below cannot race it
        with SynergyRuntime(pool(), name="rr-clean") as rt_c, \
                SynergyRuntime(pool(), name="rr-restored") as rt_r:
            srv_c = SynergyServer(cfg, params, runtime=rt_c,
                                  durable=Durability(
                                      dc, snapshot_every=6), **kw)
            for r in requests(0):          # clean batch 1: jit warmup
                srv_c.submit(r)
            srv_c.run()
            srv_r = SynergyServer.restore(cfg, params, durable=dur_x,
                                          runtime=rt_r, **kw)
            srv_r.run()                    # batch-1 remnant, untimed
            ratios, clean_samples, rec_samples = [], [], []
            for rep in range(reps):
                base = (rep + 1) * 1000
                clean_fps = timed_batch(srv_c, base)
                recovered_fps = timed_batch(srv_r, base)
                clean_samples.append(clean_fps)
                rec_samples.append(recovered_fps)
                ratios.append(recovered_fps / clean_fps)
            # graceful close: final snapshot lands, journal closes, and
            # the async writers finish before the tempdirs tear down
            clean_stats = srv_c.close(release_pool=False)
            restored_stats = srv_r.close(release_pool=False)

    # capped at 1.0: a restored server cannot genuinely beat its clean
    # twin — excess is timer noise, and capping keeps the committed
    # baseline from inflating the relative-drop gate
    rel = min(1.0, statistics.median(ratios))
    clean_fps = statistics.median(clean_samples)
    recovered_fps = statistics.median(rec_samples)
    rows = [
        {"mode": "clean-durable", "tokens_per_s_wall": clean_fps,
         "restart_recovery_rel": 1.0,
         "snapshots": clean_stats.snapshots},
        {"mode": "crashed-restored", "tokens_per_s_wall": recovered_fps,
         "restart_recovery_rel": rel,
         "snapshots": restored_stats.snapshots,
         "replayed_tokens": restored_stats.replayed_tokens,
         "replayed_jobs": restored_stats.replayed_jobs},
    ]
    return rows, {
        "restart_recovery_rel": round(rel, 4),
        "meets_0_8x": rel >= 0.8,
        "replayed_tokens": restored_stats.replayed_tokens,
        "restores": restored_stats.restores,
        "snapshots": restored_stats.snapshots,
    }


ALL = {
    "fig9_throughput": fig9_throughput,
    "fig11_latency_heterogeneity": fig11_latency_heterogeneity,
    "fig12_throughput_heterogeneity": fig12_throughput_heterogeneity,
    "fig13_work_stealing": fig13_work_stealing,
    "fig14_cluster_balance": fig14_cluster_balance,
    "table6_utilization": table6_utilization,
    "fig7_mmu_contention": fig7_mmu_contention,
    "table3_4_energy": table3_4_energy,
    "runtime_steal": runtime_steal,
    "quant_pool": quant_pool,
    "qmm_int8x8": qmm_int8x8,
    "serve_throughput": serve_throughput,
    "graph_overlap": graph_overlap,
    "qos_slo": qos_slo,
    "obs_overhead": obs_overhead,
    "fault_recovery": fault_recovery,
    "restart_recovery": restart_recovery,
}
