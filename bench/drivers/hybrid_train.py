"""Training steps of a Mamba-2/attention hybrid (Granite-4.0-H), as
``launch/train.train`` wires them.

The train driver's `Trainer` (set-up, the window's feed, the check's first
steps), its check (`gaps`) and its token stream (`tokens_for`), with what
this kind of configuration needs of its own: the mapping from its Hugging
Face ``granitemoehybrid`` config to the program's per-layer pattern
(`arch_of`), Mamba-2's initialisation of the decay and skip parameters
(`with_mamba_init`) and its plain reference
(``bench/refs/granite_hybrid.py``).

A traced run also profiles ``PROBE_STEPS`` steps of its own between the
check's first steps and the window, and counts the SSD chunk kernel's
events there: its device seconds and calls (``ssd_kernel_s``,
``ssd_kernel_calls``, per step), which ``ssd_roofline.hybrid`` reads.  The
window's own trace reports its ten longest operations only, and the
kernel's are not among them.

Traffic parameters as the train driver's: ``batch``, ``seq``,
``prefetch_depth`` and ``optimizer``.
"""
from __future__ import annotations

import math
import re
import shutil
import tempfile
import time

from bench.drivers import train
from bench.drivers.train import CHECK_STEPS, gaps, tokens_for

# Mamba-2's reference initialisation (arXiv:2405.21060, state-spaces/mamba)
A_RANGE = (1.0, 16.0)        # A uniform, A_log = log A
DT_RANGE = (1e-3, 1e-1)      # dt log-uniform, dt_bias = softplus^-1(dt)
MAMBA_STREAM = 0x55D         # the Mamba-2 leaves' keys, folded into the weights'
PROBE_STEPS = 2
# the chunk kernel's custom calls take the name of the program's jitted
# entry point, ``kernels.ssd_scan.ops.ssd``: ``%ssd``, ``%ssd.4``, ...
SSD_CALL = re.compile(r"^%ssd(\.\d+)?$")


def arch_of(c: dict):
    """The program's ArchConfig for a Hugging Face ``granitemoehybrid``
    config with dense MLPs (no experts)."""
    from repro.configs.base import ArchConfig

    if c["model_type"] != "granitemoehybrid":
        raise ValueError(f"no mapping for model_type {c['model_type']!r}")
    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("mamba_n_groups", 1), ("mamba_proj_bias", False),
                      ("mamba_d_conv", 4), ("num_local_experts", 0),
                      ("normalization_function", "rmsnorm"),
                      ("position_embedding_type", "nope"),
                      ("tie_word_embeddings", True)):
        if c[key] != want:
            raise ValueError(f"the program cannot run {key}={c[key]!r}")
    if len(c["layer_types"]) != c["num_hidden_layers"]:
        raise ValueError("layer_types must give every layer's kind")
    H, D = c["num_attention_heads"], c["hidden_size"]
    d_inner = c["mamba_expand"] * D
    if c["mamba_n_heads"] * c["mamba_d_head"] != d_inner:
        raise ValueError("mamba_n_heads x mamba_d_head != mamba_expand x "
                         "hidden_size")
    return ArchConfig(
        name=c["model_type"], family="pattern",
        n_layers=c["num_hidden_layers"], d_model=D, n_heads=H,
        n_kv_heads=c["num_key_value_heads"],
        d_ff=c["shared_intermediate_size"], vocab=c["vocab_size"],
        head_dim=D // H, ssm_state=c["mamba_d_state"],
        ssm_headdim=c["mamba_d_head"], ssm_expand=c["mamba_expand"],
        ssm_chunk=c["mamba_chunk_size"], ssm_conv_bias=c["mamba_conv_bias"],
        layer_types=tuple(c["layer_types"]),
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        logits_scaling=float(c["logits_scaling"]),
        attention_multiplier=float(c["attention_multiplier"]), rope=False,
        tie_embeddings=True, norm_eps=c["rms_norm_eps"],
        dtype=c["torch_dtype"])


def with_mamba_init(init, shapes):
    """``init`` with each Mamba-2 layer's ``A_log``, ``dt_bias`` and ``D``
    drawn as Mamba-2 initialises them, from keys folded out of the same
    key."""
    import jax
    import jax.numpy as jnp

    names = train.leaf_names(shapes)

    def draw(key, name, x):
        f32 = jnp.float32
        if name.endswith("['A_log']"):
            a = jax.random.uniform(key, x.shape, f32, *A_RANGE)
            return jnp.log(a).astype(x.dtype)
        if name.endswith("['dt_bias']"):
            lo, hi = (math.log(v) for v in DT_RANGE)
            dt = jnp.exp(jax.random.uniform(key, x.shape, f32, lo, hi))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(x.dtype)
        if name.endswith("['D']"):
            return jnp.ones(x.shape, x.dtype)
        return x

    def init_all(key):
        leaves, treedef = jax.tree.flatten(init(key))
        base = jax.random.fold_in(key, MAMBA_STREAM)
        return jax.tree.unflatten(treedef, [
            draw(jax.random.fold_in(base, i), n, x)
            for i, (n, x) in enumerate(zip(names, leaves))])

    return init_all


class Trainer(train.Trainer):
    """The train driver's `Trainer`, its weights drawn with
    `with_mamba_init`: the state is made once as the train driver makes it
    and then made again, the first freed before the second is drawn."""

    def __init__(self, r, arch, devices):
        import jax

        from repro.optim.adamw import init_opt_state

        super().__init__(r, arch, devices)
        params = self.state["params"]
        p_sh = jax.tree.map(lambda a: a.sharding, params)
        o_sh = jax.tree.map(lambda a: a.sharding, self.state["opt"])
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        self.state = params = None
        self.init = jax.jit(
            with_mamba_init(train.make_init(
                shapes, r.cell.config["initializer_range"]), shapes),
            out_shardings=p_sh)
        params = self.init(self.key)
        self.state = {"params": params,
                      "opt": jax.jit(init_opt_state,
                                     out_shardings=o_sh)(params)}


def reference_readings(r, trainer_init, key, quant=None) -> dict:
    """The reference's readings of the same steps, on the same chips."""
    import jax

    from bench.refs import granite_hybrid as ref

    c, t = r.cell.config, r.cell.traffic
    batches = [jax.device_put(tokens_for(r.seed, s, t["batch"], t["seq"],
                                         c["vocab_size"]))
               for s in range(CHECK_STEPS)]
    losses, grad, change = ref.train_readings(
        c, dict(t["optimizer"]), lambda: trainer_init(key), batches,
        quant=quant or ref.exact)
    return {"losses": losses, "grad": train._by_name(grad),
            "change": train._by_name(change)}


def _program(r):
    """The trainer set up for ``r`` and its readings of the check's steps."""
    tr = Trainer(r, arch_of(r.cell.config), r.devices)
    return tr, tr.first_steps()


def ssd_kernel_events(tdir: str) -> tuple[float, int]:
    """(device seconds, events) of the SSD chunk kernel's custom calls in
    the trace under ``tdir``, over every chip."""
    from bench import trace

    seconds, events = 0.0, 0
    for plane in trace.load(tdir).planes:
        if not plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for e in line.events:
                if SSD_CALL.match(trace.short(e.name)):
                    seconds += e.duration_ns / 1e9
                    events += 1
    return seconds, events


def probe(tr, steps: int = PROBE_STEPS) -> dict:
    """The SSD kernel's device seconds and calls per step, from a profile
    of ``steps`` more steps of the trainer."""
    import jax

    tdir = tempfile.mkdtemp(prefix="bench_probe_")
    try:
        jax.profiler.start_trace(tdir)
        try:
            for _ in range(steps):
                tr.feed()
            jax.block_until_ready(tr.state)
        finally:
            jax.profiler.stop_trace()
        seconds, events = ssd_kernel_events(tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    return {"ssd_kernel_s": seconds / steps,
            "ssd_kernel_calls": events / steps}


def run(r) -> dict:
    import gc

    import jax

    tr, prog = _program(r)
    probed = probe(tr) if r.traced else {}
    steps = failed = 0
    with r.window():
        t0 = time.perf_counter()
        pending = None
        while True:
            loss = tr.feed()
            steps += 1
            if pending is not None:
                with r.span("bench.loss_readback"):
                    failed += not math.isfinite(float(pending))
            pending = loss
            if time.perf_counter() - t0 >= r.seconds:
                break
        jax.block_until_ready(tr.state)
        failed += not math.isfinite(float(pending))
    tokens = steps * tr.batch * tr.seq
    out = {"metrics": {"train_tokens_per_s": tokens / r.window_s},
           "counters": {"steps": steps, "tokens": tokens,
                        "seq": tr.seq, "batch": tr.batch,
                        "chips": len(r.devices), **probed},
           "attempted": steps, "failed": failed}
    init, key = tr.init, tr.key
    tr.close()
    del tr
    gc.collect()
    ref = reference_readings(r, init, key)
    out["compared"] = [(k, v, r.cell.limits[k])
                       for k, v in gaps(prog, ref).items()]
    return out


def readings(r, control: bool = True) -> dict:
    """The check's numbers for the program and, with ``control``, for the
    control (the reference in float8 in the program's place) on one seed,
    with no window: what the limits are set from."""
    import gc

    from bench.refs import granite_hybrid as ref

    tr, prog = _program(r)
    init, key = tr.init, tr.key
    tr.close()
    del tr
    gc.collect()
    want = reference_readings(r, init, key)
    out = {"program": gaps(prog, want)}
    if control:
        ctl = reference_readings(r, init, key, quant=ref.fp8)
        out["control"] = gaps(ctl, want)
    return out
