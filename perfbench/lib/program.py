"""The system under test: the program's own compiled train step for a cell.

Built through the program's entry points: ``launch.train.build_runtime``
for the model and plan, ``launch.mesh.make_mesh`` for the mesh,
``train.step.compile_train_step`` for the step and its shardings, and the
optimizer's ``opt_init`` for the AdamW state.  The starting weights come
from the benchmark (``lib.weights``), in the plan's shardings.
"""

from __future__ import annotations

from time import perf_counter

import jax

from perfbench.lib import weights


class Program:
    def __init__(self, cell, devices, reduced: bool = False):
        from repro.configs.base import ShapeConfig
        from repro.launch.mesh import make_mesh
        from repro.launch.train import build_runtime
        from repro.train.optimizer import OptConfig, opt_init
        from repro.train.step import compile_train_step

        conf, traffic = cell.config, cell.traffic
        cfg, model, plan = build_runtime(conf["arch"], reduced, traffic["plan"],
                                         remat=False)
        differ = {k: (v, getattr(cfg, k)) for k, v in conf["model"].items()
                  if getattr(cfg, k) != v}
        if differ:
            raise SystemExit(f"the program's {conf['arch']} differs from "
                             f"{conf['name']}.json (file, program): {differ}")
        if plan.dp * plan.tp != len(devices):
            raise SystemExit(f"plan {traffic['plan']} needs {plan.dp * plan.tp} "
                             f"chips, the cell has {len(devices)}")
        self.optcfg = OptConfig(**conf["optimizer"])
        self.mesh = make_mesh(plan.dp, plan.tp, devices=devices)
        self.batch, self.seq = traffic["global_batch"], traffic["seq_len"]
        specs = model.input_specs(ShapeConfig("train", self.seq, self.batch,
                                              "train"))
        lowered, p_shard, o_shard, self.b_shard = compile_train_step(
            model, plan, self.mesh, self.optcfg, specs)
        self.step = lowered.compile()
        self.hbm_peak_bytes = self.step.memory_analysis().peak_memory_in_bytes
        self.shapes = weights.flat(jax.eval_shape(model.init,
                                                  jax.random.PRNGKey(0)))
        shapes, optcfg = self.shapes, self.optcfg

        def init(key):
            params = weights.unflat({p: weights.leaf(key, p, s.shape, s.dtype)
                                     for p, s in shapes.items()})
            return params, opt_init(params, optcfg)

        self._init = jax.jit(init, out_shardings=(p_shard, o_shard))

    def init(self, key):
        """(params, AdamW state) from the seed's weights, on the devices."""
        return self._init(key)

    def train_step(self, params, opt_state, tokens, k: int):
        """Step ``k`` as the launcher takes it: make the batch (``tokens``:
        step -> host batch) and feed it to the plan's batch sharding, run
        the compiled step, wait for it.  Returns (params, opt_state, metrics,
        (feed, dispatch, sync) seconds)."""
        t0 = perf_counter()
        with jax.profiler.TraceAnnotation("step"):
            with jax.profiler.TraceAnnotation("feed"):
                batch = jax.device_put({"tokens": tokens(k)}, self.b_shard)
            t1 = perf_counter()
            with jax.profiler.TraceAnnotation("dispatch"):
                params, opt_state, metrics = self.step(params, opt_state, batch)
            t2 = perf_counter()
            with jax.profiler.TraceAnnotation("sync"):
                jax.block_until_ready((params, opt_state, metrics))
        return params, opt_state, metrics, (t1 - t0, t2 - t1, perf_counter() - t2)

    def first_steps(self, params, opt_state, tokens, key, n: int = 3):
        """The steps the reference follows, through ``train_step``.

        ``tokens``: a callable step -> host batch.  Returns (params,
        opt_state, readings): each step's loss, each leaf's first gradient
        norm before the clip (worked out from the first moment after one
        step and the global norm the step reports) and a sample of its
        elements (``weights.sample``), and each leaf's change after the
        ``n`` steps."""
        o = self.optcfg
        losses, grad_norms = [], None
        for k in range(n):
            params, opt_state, met, _ = self.train_step(params, opt_state,
                                                        tokens, k)
            losses.append(float(met["loss"]))
            if k == 0:
                gn = float(met["grad_norm"])
                scale = min(1.0, o.grad_clip / (gn + 1e-9)) if o.grad_clip > 0 else 1.0
                m1 = weights.flat(opt_state["m"])
                grad_norms = {p: v / ((1.0 - o.b1) * scale)
                              for p, v in weights.norms(m1).items()}
                grad_sample = {p: v / ((1.0 - o.b1) * scale)
                               for p, v in weights.sample(m1, key).items()}
        change = weights.change_norms(weights.flat(params), key)
        return params, opt_state, {"losses": losses, "grad_norms": grad_norms,
                                   "grad_sample": grad_sample,
                                   "change_norms": change}
