"""Training steps of a decoder configuration, as ``launch/train.train`` wires them.

Set-up builds one train state on the cell's chips from the seed (weights
made by the benchmark in one jitted call, in the dtypes the program stores
them in; the program's AdamW state), the program's train step jitted with
the state donated, and the program's prefetching loader.  It then drives
that same state through the first steps with the window's own feed (loader
``get``, ``device_put`` to the batch shardings, step dispatch) and keeps
what the check needs: the three losses, the first gradient's norm per leaf
as the optimizer received it (from the first moment after one step) and
each leaf's change after three steps.  The window runs more steps of the
same object; after it the state is freed and the configuration's plain
reference (``bench/refs/qwen3.py``) runs the same three steps from the same
weights and tokens.

Traffic parameters (``bench/traffic/<mix>.json``): ``batch``, ``seq``,
``prefetch_depth`` and ``optimizer`` (AdamW hyperparameters).  Tokens are
uniform over the vocabulary, drawn per step from the seed by `tokens_for`.
"""
from __future__ import annotations

import math
import time

import numpy as np

CHECK_STEPS = 3
WEIGHT_STREAM = 0x5EED  # separates the weights' random stream from the data's


def tokens_for(seed: int, step: int, batch: int, seq: int,
               vocab: int) -> np.ndarray:
    """The step's (batch, seq) tokens: a copy of the program's data
    generator (``data/pipeline.batch_for_step``), kept so that the
    reference draws its inputs without the program."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    return rng.integers(0, vocab, (batch, seq), dtype=np.int32)


def arch_of(c: dict):
    """The program's ArchConfig for a Hugging Face style decoder config."""
    from repro.configs.base import ArchConfig

    if c["model_type"] != "qwen3":
        raise ValueError(f"no mapping for model_type {c['model_type']!r}")
    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False),
                      ("use_sliding_window", False)):
        if c[key] != want:
            raise ValueError(f"the program cannot run {key}={c[key]!r}")
    return ArchConfig(
        name=c["model_type"], family="dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        head_dim=c["head_dim"], qk_norm=True,
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        dtype=c["torch_dtype"])


def weight_key(seed: int):
    import jax

    state = np.random.SeedSequence([seed, WEIGHT_STREAM]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(state, np.uint32))


def make_init(shapes, std: float):
    """``init(key) -> params`` in the structure and dtypes of ``shapes``:
    every norm scale 1, every other leaf normal with deviation ``std``."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def init(key):
        keys = jax.random.split(key, len(flat))
        leaves = []
        for k, (path, s) in zip(keys, flat):
            if "norm" in jax.tree_util.keystr(path[-1:]):
                leaves.append(jnp.ones(s.shape, s.dtype))
            else:
                leaves.append((jax.random.normal(k, s.shape, jnp.float32)
                               * std).astype(s.dtype))
        return jax.tree.unflatten(treedef, leaves)

    return init


def leaf_names(tree) -> list[str]:
    import jax

    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _norms(tree):
    import jax
    import jax.numpy as jnp

    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


class Trainer:
    """The object set-up builds and the window drives."""

    def __init__(self, r, arch, devices):
        import jax

        from repro.configs.base import ShapeConfig
        from repro.data import DataConfig, PrefetchingLoader
        from repro.distributed.sharding import default_rules, shardings_for
        from repro.launch.mesh import make_host_mesh
        from repro.optim.adamw import AdamWConfig, init_opt_state
        from repro.runtime.train_step import (
            batch_axes_for, batch_shardings, build_train_step,
            make_train_state,
        )

        t, c = r.cell.traffic, r.cell.config
        self.r = r
        self.batch, self.seq = t["batch"], t["seq"]
        self.opt = AdamWConfig(**t["optimizer"])
        rules = default_rules(make_host_mesh(devices=devices))
        axes = {}

        def shape_only(key):
            state, a = make_train_state(arch, key)
            axes.update(a)
            return state

        shapes = jax.eval_shape(shape_only, jax.random.PRNGKey(0))
        st_sh = shardings_for(rules, axes, shapes)
        self.init = jax.jit(make_init(shapes["params"],
                                      c["initializer_range"]),
                            out_shardings=st_sh["params"])
        self.key = weight_key(r.seed)

        # the same compiled init makes the weights here, again for the
        # check's change after three steps, and for the reference: two
        # programs drawing the same normals may round them differently
        params = self.init(self.key)
        self.state = {"params": params,
                      "opt": jax.jit(init_opt_state,
                                     out_shardings=st_sh["opt"])(params)}
        self.b_sh = batch_shardings(rules, batch_axes_for(arch, "train"))
        self.step = jax.jit(build_train_step(arch, rules, self.opt),
                            donate_argnums=(0,))
        self.loader = PrefetchingLoader(
            arch, ShapeConfig("bench", self.seq, self.batch, "train"),
            DataConfig(seed=r.seed, depth=t["prefetch_depth"]))
        self.names = leaf_names(shapes["params"])

    def feed(self):
        """One step through the window's own call and feed."""
        import jax

        r = self.r
        with r.span("bench.loader_get"):
            batch = self.loader.get()
        with r.span("bench.device_put"):
            batch = jax.device_put(batch, self.b_sh)
        with r.span("bench.step_dispatch"):
            self.state, metrics = self.step(self.state, batch)
        return metrics["loss"]

    def first_steps(self) -> dict:
        """The check's readings of steps 1..CHECK_STEPS."""
        import jax

        losses = [float(self.feed())]
        mu = self.state["opt"]["mu"]
        grad = [float(n) / (1 - self.opt.b1)
                for n in jax.jit(_norms)(mu)]
        for _ in range(CHECK_STEPS - 1):
            losses.append(float(self.feed()))
        change = jax.jit(lambda p, p0: _norms(jax.tree.map(
            lambda a, b: a.astype(np.float32) - b.astype(np.float32),
            p, p0)))(self.state["params"], self.init(self.key))
        return {"losses": losses, "grad": dict(zip(self.names, grad)),
                "change": dict(zip(self.names, map(float, change)))}

    def close(self):
        self.loader.close()
        self.state = None


def _worst(prog: dict, ref: dict, keep=None) -> float:
    """Largest gap between the program's and the reference's norm of a
    leaf, over the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    med = float(np.median(list(ref.values())))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med)
               for k in ref if keep is None or k in keep)


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers the check compares.

    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone under AdamW; their change is left out."""
    gmed = float(np.median(list(ref["grad"].values())))
    moving = {k for k, g in ref["grad"].items() if g >= 1e-3 * gmed}
    return {"loss_gap": max(abs(a - b) / abs(b) for a, b in
                            zip(prog["losses"], ref["losses"])),
            "grad_norm_gap": _worst(prog["grad"], ref["grad"]),
            "change_norm_gap": _worst(prog["change"], ref["change"], moving)}


def reference_readings(r, trainer_init, key, quant=None) -> dict:
    """The reference's readings of the same steps, on the same chips."""
    import jax

    from bench.refs import qwen3 as ref

    c, t = r.cell.config, r.cell.traffic
    batches = [jax.device_put(tokens_for(r.seed, s, t["batch"], t["seq"],
                                         c["vocab_size"]))
               for s in range(CHECK_STEPS)]
    opt = dict(t["optimizer"])
    losses, grad, change = ref.train_readings(
        c, opt, lambda: trainer_init(key), batches,
        quant=quant or ref.exact)
    return {"losses": losses, "grad": _by_name(grad),
            "change": _by_name(change)}


def _by_name(tree) -> dict:
    import jax

    return dict(zip(leaf_names(tree), jax.tree.leaves(tree)))


def run(r) -> dict:
    import gc

    import jax

    arch = arch_of(r.cell.config)
    tr = Trainer(r, arch, r.devices)
    prog = tr.first_steps()
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
                        "chips": len(r.devices)},
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

    from bench.refs import qwen3 as ref

    tr = Trainer(r, arch_of(r.cell.config), r.devices)
    prog = tr.first_steps()
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
