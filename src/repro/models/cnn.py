"""CNNs via conv-as-tiled-GEMM — the paper's own benchmark networks, and
ResNet-50 v1.5 (He et al., arXiv:1512.03385) from the same layer kinds
plus bottleneck blocks.

Every CONV layer lowers to im2col + :func:`synergy_matmul` (so its tile-job
decomposition is visible to the schedulers), pooling/activation/FC stay on
the "CPU side" exactly as in the paper (§3.1.4).  ``build_simnet`` exports
a linear conv/pool/fc network as a :class:`repro.core.scheduler.SimNet`
for the discrete-event runtime reproduction.

Layer dims are modeled from the Darknet/Caffe configs the paper trained
(Table 2); per-frame op counts land within ~10-20% of the paper's reported
GOPS-at-fps for MNIST and CIFAR_full (Table 4), which is what the scheduler
trends depend on.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.im2col import conv_out_shape, im2col_operand, view_operand
from repro.core.job import JobSet
from repro.core.scheduler import SimLayer, SimNet
from repro.core.synergy_mm import synergy_matmul
from repro.obs.trace import annotate

__all__ = ["CNNConfig", "init_cnn", "cnn_forward", "build_simnet",
           "conv_jobsets", "conv_graph_steps", "conv_wave_graph",
           "maxpool2d", "maxpool_window", "fold_batchnorm",
           "cnn_flops_per_frame"]


def maxpool2d(x: jax.Array, size: int) -> jax.Array:
    """Non-overlapping max pool (stride == size), cropping odd edges —
    the paper's CPU-side pooling (§3.1.4).  ONE implementation shared by
    ``cnn_forward`` and the serving prefill chain, so their activations
    cannot silently diverge."""
    n, h, w, c = x.shape
    x = x[:, : h - h % size, : w - w % size, :]
    return x.reshape(n, h // size, size, w // size, size, c).max(axis=(2, 4))


def maxpool_window(x: jax.Array, k: int, stride: int, pad: int) -> jax.Array:
    """Max pool over k x k windows at ``stride``, the border padded with
    -inf by ``pad`` (windows may overlap): ResNet's 3x3/2 pad-1 pool."""
    return jax.lax.reduce_window(
        x, jnp.array(-jnp.inf, x.dtype), jax.lax.max, (1, k, k, 1),
        (1, stride, stride, 1), ((0, 0), (pad, pad), (pad, pad), (0, 0)))


def fold_batchnorm(w: jax.Array, b: jax.Array, gamma: jax.Array,
                   beta: jax.Array, mean: jax.Array, var: jax.Array,
                   eps: float = 1e-5) -> tuple[jax.Array, jax.Array]:
    """A convolution (HWIO ``w``, bias ``b``) followed by inference batch
    norm, as one convolution: ``(w', b')`` with
    ``conv(x, w') + b' == gamma * (conv(x, w) + b - mean) /
    sqrt(var + eps) + beta``.  How a trained checkpoint's BN layers are
    loaded into a network's ``conv``/``bottleneck`` parameters."""
    scale = gamma / jnp.sqrt(var + eps)
    return w * scale, (b - mean) * scale + beta


# layer spec forms:
#   ("conv", cout, k, stride, pad)   conv + ReLU
#   ("pool", size)                   max pool, stride == size, odd edges cut
#   ("fc", n_out)                    ReLU except on the last fc
#   ("maxpool", k, stride, pad)      max pool, -inf padding, may overlap
#   ("bottleneck", width, cout, stride)
#       relu(c(b(a(x))) + shortcut(x)): a 1x1 to width + ReLU, b 3x3/stride
#       pad 1 + ReLU, c 1x1 to cout; the shortcut a 1x1/stride projection
#       where stride != 1 or cin != cout, else the identity (ResNet v1.5)
#   ("gap",)                         mean over H and W -> (N, C)
Layer = tuple
KINDS = ("conv", "pool", "fc", "maxpool", "bottleneck", "gap")


def block_convs(spec: Layer, cin: int) -> list[tuple]:
    """``(part, k, stride, pad, cin, cout, relu)`` of each convolution of a
    bottleneck ``spec`` whose input has ``cin`` channels, in the order
    they run; the ``proj`` shortcut only where the block needs one."""
    _, width, cout, s = spec
    convs = [("a", 1, 1, 0, cin, width, True),
             ("b", 3, s, 1, width, width, True),
             ("c", 1, 1, 0, width, cout, False)]
    if projects(spec, cin):
        convs.append(("proj", 1, s, 0, cin, cout, False))
    return convs


def projects(spec: Layer, cin: int) -> bool:
    """Whether a bottleneck ``spec`` on ``cin`` channels has a projection
    shortcut (ResNet's option B): where it strides or widens."""
    return spec[3] != 1 or cin != spec[2]


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    name: str
    input_hw: int
    cin: int
    layers: tuple[Layer, ...]
    num_classes: int = 10
    tile: int = 32            # the paper's TS=32

    def trace_shapes(self):
        """Walk the net, yielding (layer, h, w, c_in) before each layer."""
        h = w = self.input_hw
        c = self.cin
        out = []
        for spec in self.layers:
            out.append((spec, h, w, c))
            if spec[0] == "conv":
                _, cout, k, s, p = spec
                h, w = conv_out_shape(h, w, k, k, s, p)
                c = cout
            elif spec[0] == "pool":
                size = spec[1]
                h, w = h // size, w // size
            elif spec[0] == "fc":
                h = w = 1
                c = spec[1]
            elif spec[0] == "maxpool":
                _, k, s, p = spec
                h, w = conv_out_shape(h, w, k, k, s, p)
            elif spec[0] == "bottleneck":
                h, w = conv_out_shape(h, w, 3, 3, spec[3], 1)
                c = spec[2]
            elif spec[0] == "gap":
                h = w = 1
            else:
                raise ValueError(f"{self.name}: unknown layer kind "
                                 f"{spec[0]!r} (known: {KINDS})")
        return out, (h, w, c)

    def check_linear_chain(self, what: str) -> None:
        """Raise unless the net is a linear conv/pool/fc chain, the only
        form ``what`` (the DES export, the serving prefill graph) takes."""
        for spec in self.layers:
            if spec[0] not in ("conv", "pool", "fc"):
                raise NotImplementedError(
                    f"{what} takes a linear conv/pool/fc chain; "
                    f"{self.name} has a {spec[0]!r} layer")


def init_cnn(cfg: CNNConfig, key: jax.Array, dtype=jnp.float32) -> dict:
    params = {}
    shapes, _ = cfg.trace_shapes()
    for i, (spec, h, w, c) in enumerate(shapes):
        if spec[0] == "conv":
            _, cout, k, s, p = spec
            key, sub = jax.random.split(key)
            scale = (2.0 / (k * k * c)) ** 0.5
            params[f"conv{i}_w"] = (jax.random.normal(sub, (k, k, c, cout)) * scale).astype(dtype)
            params[f"conv{i}_b"] = jnp.zeros((cout,), dtype)
        elif spec[0] == "fc":
            n_in = h * w * c
            n_out = spec[1]
            key, sub = jax.random.split(key)
            scale = (2.0 / n_in) ** 0.5
            params[f"fc{i}_w"] = (jax.random.normal(sub, (n_in, n_out)) * scale).astype(dtype)
            params[f"fc{i}_b"] = jnp.zeros((n_out,), dtype)
        elif spec[0] == "bottleneck":
            for part, k, _, _, ci, co, _ in block_convs(spec, c):
                key, sub = jax.random.split(key)
                # He scale; the branch's last conv starts small, as a
                # trained block's last BN scale is, so the residual sum
                # stays of unit order through the stack
                scale = ((2.0 / (k * k * ci)) ** 0.5
                         * (0.2 if part == "c" else 1.0))
                params[f"block{i}_{part}_w"] = (
                    jax.random.normal(sub, (k, k, ci, co)) * scale
                ).astype(dtype)
                params[f"block{i}_{part}_b"] = jnp.zeros((co,), dtype)
    return params


def _conv_via_jobs(x, w, b, stride, pad, tile, name, engine=None,
                   job_class=None, activation=jax.nn.relu):
    """CONV -> im2col -> synergy_matmul (tile jobs) -> bias + activation
    epilogue.  The A operand comes from the jitted patch program, except
    that a 1x1 convolution without padding reads it straight from the
    (strided) input: no gather.  The span's ``path`` tag says which."""
    kh, kw, cin, cout = w.shape
    n, h, wd, _ = x.shape
    oh, ow = conv_out_shape(h, wd, kh, kw, stride, pad)
    view = kh == kw == 1 and pad == 0
    with annotate("repro/cnn/im2col", path="view" if view else "patches"):
        if view:
            a = view_operand(x, stride)
        else:
            a = im2col_operand(x, kh, kw, stride, pad)
    y = synergy_matmul(a, w.reshape(-1, cout), bias=b,
                       activation=activation, tile=tile, name=name,
                       engine=engine, job_class=job_class)
    return y.reshape(n, oh, ow, cout)


def _bottleneck(x, params, i, spec, tile, name, engine, job_class):
    """One ResNet v1.5 bottleneck block (layer ``i``): its convolutions
    through :func:`_conv_via_jobs`, then ``relu(branch + shortcut)``."""
    def conv(src, part, s, p, relu):
        return _conv_via_jobs(
            src, params[f"block{i}_{part}_w"], params[f"block{i}_{part}_b"],
            s, p, tile, f"{name}/block{i}_{part}", engine=engine,
            job_class=job_class, activation=jax.nn.relu if relu else None)

    y = shortcut = x
    for part, _, s, p, _, _, relu in block_convs(spec, x.shape[-1]):
        if part == "proj":
            shortcut = conv(x, part, s, p, relu)
        else:
            y = conv(y, part, s, p, relu)
    with annotate("repro/cnn/residual"):
        return jax.nn.relu(y + shortcut)


def cnn_forward(cfg: CNNConfig, params: dict, x: jax.Array, *,
                engine: str | None = None,
                job_class: str | None = None,
                runtime=None) -> jax.Array:
    """x: (N, H, W, Cin) -> logits (N, num_classes).

    ``engine``: pin every GEMM to a registered engine; None lets the
    dispatcher rank capable engines per GEMM (the default).
    ``job_class``: precision-routing policy for every GEMM
    (:data:`repro.engines.JOB_CLASSES`) — ``"decode"`` prefers registered
    int8 engines (error-tolerant inference), ``"train"`` requires
    grad-safe full-precision paths.
    ``runtime``: a :class:`repro.soc.SynergyRuntime` — every CONV/FC GEMM
    is split across its engine pool and balanced by work stealing (with
    ``engine`` demoted to a queue-affinity hint).  Don't combine with
    ``jax.jit`` — traced arrays fall back to single-engine dispatch."""
    import contextlib
    if runtime is not None:
        from repro.soc import runtime_scope
        scope = runtime_scope(runtime)
    else:
        scope = contextlib.nullcontext()
    with annotate("repro/cnn/forward"), scope:
        return _cnn_forward(cfg, params, x, engine=engine,
                            job_class=job_class)


def _cnn_forward(cfg: CNNConfig, params: dict, x: jax.Array, *,
                 engine: str | None = None,
                 job_class: str | None = None) -> jax.Array:
    shapes, _ = cfg.trace_shapes()
    stage = index = 0
    for i, (spec, _, _, c) in enumerate(shapes):
        if spec[0] == "conv":
            _, cout, k, s, p = spec
            x = _conv_via_jobs(x, params[f"conv{i}_w"], params[f"conv{i}_b"],
                               s, p, cfg.tile, f"{cfg.name}/conv{i}",
                               engine=engine, job_class=job_class)
        elif spec[0] == "pool":
            with annotate("repro/cnn/pool"):
                x = maxpool2d(x, spec[1])
        elif spec[0] == "maxpool":
            with annotate("repro/cnn/pool"):
                x = maxpool_window(x, *spec[1:])
        elif spec[0] == "bottleneck":
            # a stage starts at each block with a projection shortcut
            if projects(spec, c):
                stage, index = stage + 1, 0
            with annotate("repro/cnn/block", stage=stage, index=index):
                x = _bottleneck(x, params, i, spec, cfg.tile, cfg.name,
                                engine, job_class)
            index += 1
        elif spec[0] == "gap":
            with annotate("repro/cnn/gap"):
                x = x.mean(axis=(1, 2))
        elif spec[0] == "fc":
            n = x.shape[0]
            x = x.reshape(n, -1)
            last = all(s2[0] != "fc" for s2, *_ in shapes[i + 1:])
            act = None if last else jax.nn.relu
            x = synergy_matmul(x, params[f"fc{i}_w"], bias=params[f"fc{i}_b"],
                               activation=act, tile=cfg.tile,
                               name=f"{cfg.name}/fc{i}", engine=engine,
                               job_class=job_class)
    return x


def cnn_flops_per_frame(cfg: CNNConfig) -> int:
    total = 0
    shapes, _ = cfg.trace_shapes()
    for spec, h, w, c in shapes:
        if spec[0] == "conv":
            _, cout, k, s, p = spec
            oh, ow = conv_out_shape(h, w, k, k, s, p)
            total += 2 * oh * ow * cout * k * k * c
        elif spec[0] == "fc":
            total += 2 * h * w * c * spec[1]
        elif spec[0] == "bottleneck":
            oh, ow = conv_out_shape(h, w, 3, 3, spec[3], 1)
            for part, k, _, _, ci, co, _ in block_convs(spec, c):
                pixels = h * w if part == "a" else oh * ow
                total += 2 * pixels * co * k * k * ci
    return total


def conv_jobsets(cfg: CNNConfig, n_frames: int = 1, *,
                 tile: int | tuple | None = None,
                 name_prefix: str = "") -> list[tuple[int, JobSet]]:
    """The per-CONV-layer im2col GEMM JobSets of an ``n_frames`` image
    batch: ``[(layer_index, JobSet), ...]`` in network order.

    This is the ONE conv-as-GEMM shape source shared by the DES exporter
    (:func:`build_simnet`, ``n_frames=1``) and the serving prefill path
    (``n_frames`` = all frames of an admission wave), so server prefill
    busy-seconds and simulator busy-seconds read the same cost model over
    the same jobs by construction."""
    cfg.check_linear_chain("conv_jobsets")
    out: list[tuple[int, JobSet]] = []
    shapes, _ = cfg.trace_shapes()
    conv_id = 0
    for i, (spec, h, w, c) in enumerate(shapes):
        if spec[0] != "conv":
            continue
        _, cout, k, s, p = spec
        js = JobSet.for_conv(conv_id, n_frames, h, w, c, cout, k, s, p,
                             tile if tile is not None else cfg.tile,
                             name=f"{name_prefix}{cfg.name}/conv{i}")
        out.append((i, js))
        conv_id += 1
    return out


def conv_graph_steps(cfg: CNNConfig) -> list[tuple]:
    """Per-CONV-layer dataflow geometry for graph construction:
    ``[(layer_index, pools_before, (k, stride, pad), (oh, ow, cout)),
    ...]`` in network order, where ``pools_before`` are the CPU-side max
    pool sizes between the previous conv and this one.  The conv
    front-end ends at the first FC layer (matching the serving prefill
    chain)."""
    cfg.check_linear_chain("conv_graph_steps")
    out: list[tuple] = []
    shapes, _ = cfg.trace_shapes()
    pools: list[int] = []
    for i, (spec, h, w, c) in enumerate(shapes):
        if spec[0] == "pool":
            pools.append(spec[1])
        elif spec[0] == "conv":
            _, cout, k, s, p = spec
            oh, ow = conv_out_shape(h, w, k, k, s, p)
            out.append((i, tuple(pools), (k, s, p), (oh, ow, cout)))
            pools = []
        else:                         # fc: conv front-end ends here
            break
    return out


def conv_wave_graph(cfg: CNNConfig, params: dict, x0: jax.Array,
                    steps: Sequence[tuple], jobsets: Sequence[JobSet],
                    n_frames: int, *, in_shape: tuple | None = None,
                    affinity: str | None = None,
                    job_class: str | None = "prefill",
                    im2col_fn=None, qos=None):
    """Build the ``(nodes, edges)`` dataflow graph of one prefill wave's
    conv front-end over a consecutive slice of :func:`conv_graph_steps`.

    Layer *l* becomes two nodes: a HOST gather node (reshape the previous
    GEMM's flat output, apply the CPU-side pools, one
    :func:`~repro.core.im2col.im2col_wave` over the whole wave) and a
    GEMM node (``submit_gemm`` of the im2col panel against the conv
    weights) — so layer *l+1*'s gather overlaps layer *l*'s GEMM compute,
    the NEURAghe-style producer/consumer overlap the chain never had.

    ``x0``: the slice's input — the stacked wave frames for the first
    chunk, or the previous chunk's flat GEMM output (then pass
    ``in_shape`` to restore (N, H, W, C)).  The LAST node's value is the
    final conv's flat ``(m, cout)`` output.  ``im2col_fn`` overrides the
    gather primitive (the serving engine passes its own module reference
    so instrumentation hooks on that module see every wave gather);
    ``qos`` attaches a :class:`repro.soc.qos_policy.QosTag` to every GEMM
    node's panels, so a chunked prefill wave schedules at its tenants'
    class and decode-class work preempts it at chunk boundaries."""
    from repro.core.im2col import im2col_wave
    from repro.soc.graph import GraphNode
    if im2col_fn is None:
        im2col_fn = im2col_wave

    nodes: list = []
    edges: list[tuple[int, int]] = []
    prev_gemm: int | None = None
    prev_shape = in_shape
    for (i, pools, (k, s, p), (oh, ow, cout)), js in zip(steps, jobsets):

        def gather(rt, *pred, _pools=pools, _k=k, _s=s, _p=p,
                   _shape=prev_shape):
            x = pred[0].reshape(_shape) if pred else (
                x0.reshape(_shape) if _shape is not None else x0)
            for size in _pools:
                x = maxpool2d(x, size)
            return im2col_fn(x, _k, _k, _s, _p)

        def gemm(rt, a, _i=i, _js=js, _cout=cout):
            return rt.submit_gemm(
                a, params[f"conv{_i}_w"].reshape(-1, _cout), jobset=_js,
                bias=params[f"conv{_i}_b"], activation=jax.nn.relu,
                tile=(_js.ts_m, _js.ts_n, _js.ts_k), job_class=job_class,
                affinity=affinity, qos=qos)

        gi = len(nodes)
        nodes.append(GraphNode(name=f"{js.name}/gather", run=gather))
        if prev_gemm is not None:
            edges.append((prev_gemm, gi))
        nodes.append(GraphNode(name=js.name, run=gemm))
        edges.append((gi, gi + 1))
        prev_gemm = gi + 1
        prev_shape = (n_frames, oh, ow, cout)
    return nodes, edges


def build_simnet(cfg: CNNConfig) -> SimNet:
    """Export as a SimNet for the discrete-event runtime simulator.

    CONV layers -> accelerated tile-job stages (+ im2col CPU cost);
    pool/fc -> CPU stages; plus the paper's normalization preprocessing."""
    cfg.check_linear_chain("build_simnet")
    layers: list[SimLayer] = []
    shapes, _ = cfg.trace_shapes()
    # normalization / scaling preprocessing (§3.1.4)
    n_in_elems = cfg.input_hw * cfg.input_hw * cfg.cin
    layers.append(SimLayer("norm", "cpu", cpu_ops=4 * n_in_elems))
    # DES layer names are bare conv{i} (no net prefix): keep them stable
    conv_js = {i: dataclasses.replace(js, name=f"conv{i}")
               for i, js in conv_jobsets(cfg)}
    for i, (spec, h, w, c) in enumerate(shapes):
        if spec[0] == "conv":
            js = conv_js[i]
            # im2col writes m*k floats (fp32), reads input once
            layers.append(SimLayer(f"conv{i}", "conv", jobset=js,
                                   im2col_bytes=4 * (js.m * js.k
                                                     + h * w * c)))
        elif spec[0] == "pool":
            size = spec[1]
            layers.append(SimLayer(f"pool{i}", "cpu",
                                   cpu_ops=h * w * c))
        elif spec[0] == "fc":
            layers.append(SimLayer(f"fc{i}", "cpu",
                                   cpu_ops=2 * h * w * c * spec[1]))
    return SimNet(cfg.name, tuple(layers))
