"""Distributed train step: pjit + logical sharding rules, ZeRO-1 optimizer,
optional compressed cross-pod gradient reduction, optional in-step egress
packing for the in-transit sink (the paper's producer side).

The returned `step_fn` is jit'd with explicit in/out shardings and state
donation; `abstract_state()` + `repro.configs.input_specs` are everything
the multi-pod dry-run needs (no allocation).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.layers import abstract_params, init_params, param_shardings
from repro.models.model import Model
from repro.optim import grad_compress
from repro.optim.optimizer import AdamWConfig, make_optimizer, opt_state_specs
from repro.optim.schedule import warmup_cosine
from repro.train.sharding import batch_shardings, make_rules

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: AdamWConfig = AdamWConfig()
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    compress_pods: bool = False      # int8 EF cross-pod gradient reduction
    egress: str = "diag"             # none | diag | grads_int8
    egress_blocks: int = 64          # int8 blocks sampled for egress
    xent_chunk: int = 512
    microbatches: int = 1            # gradient accumulation (activation
                                     # memory / microbatches; grads fp32)
    fsdp_experts: bool = False       # shard expert ffn dim over `data`
                                     # (FSDP: per-layer weight all-gather;
                                     # required for 400B+ MoE to fit HBM)


class TrainSetup:
    def __init__(self, model: Model, mesh, cfg: TrainConfig = TrainConfig()):
        self.model = model
        self.mesh = mesh
        self.cfg = cfg
        rules = make_rules(mesh, model.cfg)
        if cfg.fsdp_experts:
            rules["expert_ffn"] = "data"
        self.rules = dict(rules, __zero1__=rules["batch"])
        self.spec_tree = model.param_specs()
        self.opt_specs = opt_state_specs(self.spec_tree, cfg.opt, mesh,
                                         self.rules)
        self._init_opt, self._update = make_optimizer(
            self.spec_tree, cfg.opt, mesh, self.rules)
        self.compress = cfg.compress_pods and "pod" in mesh.axis_names \
            and mesh.shape["pod"] > 1

    # -- state ------------------------------------------------------------
    def state_specs(self) -> dict:
        from repro.models.layers import ParamSpec
        s = {"params": self.spec_tree, "opt": self.opt_specs,
             "step": ParamSpec((), (), jnp.int32, init="zeros")}
        if self.compress:
            n_pods = self.mesh.shape["pod"]
            err = grad_compress.error_state(
                abstract_params(self.spec_tree), n_pods)
            s["err"] = ParamSpec((n_pods, *err.shape),
                                 ("__pod__", None, None), jnp.float32,
                                 init="zeros")
        return s

    def state_shardings(self) -> dict:
        rules = dict(self.rules, __pod__="pod")
        return param_shardings(self.state_specs(), self.mesh, rules)

    def abstract_state(self) -> dict:
        return abstract_params(self.state_specs())

    def init_state(self, key: jax.Array) -> dict:
        """Materialize the state already laid out on the mesh (an unsharded
        full-width state would not fit one chip)."""
        init = functools.partial(init_params, self.state_specs())
        return jax.jit(init, out_shardings=self.state_shardings())(key)

    # -- the step -----------------------------------------------------------
    def _loss(self, params: PyTree, batch: dict):
        return self.model.loss_fn(params, batch, self.rules,
                                  xent_chunk=self.cfg.xent_chunk)

    def _egress(self, grads: PyTree, loss, gnorm):
        if self.cfg.egress == "none":
            return {}
        diag = jnp.stack([loss.astype(jnp.float32), gnorm])
        if self.cfg.egress == "diag":
            return {"diag": diag}
        # grads_int8: pack a fixed sample of gradient blocks through the
        # staging_pack XLA twin (the Pallas kernel is the TPU version)
        from repro.kernels.staging_pack import ref as pack_ref
        nb = self.cfg.egress_blocks
        flat = jnp.concatenate(
            [g.reshape(-1)[: nb * 1024].astype(jnp.float32)
             for g in jax.tree.leaves(grads)][:1])
        pad = (-flat.size) % (nb * 1024)
        flat = jnp.pad(flat, (0, pad)).reshape(nb * 8, 128)
        blocks, scales = pack_ref.pack_blocks_ref(
            flat, tile=(8, 128), out_dtype=jnp.int8)
        return {"diag": diag, "blocks": blocks, "scales": scales}

    def step_fn(self) -> Callable:
        cfg = self.cfg

        def train_step(state: dict, batch: dict):
            lr = warmup_cosine(state["step"], peak_lr=cfg.peak_lr,
                               warmup_steps=cfg.warmup_steps,
                               total_steps=cfg.total_steps)
            grad_fn = jax.value_and_grad(self._loss, has_aux=True)

            if self.compress:
                n_pods = self.mesh.shape["pod"]

                def body(params, batch_pod, err_pod):
                    (loss, metrics), grads = grad_fn(params, batch_pod)
                    # _flatten row-pads to a multiple of n_pods (ring RS
                    # needs n|rows), matching error_state's layout.
                    flat, pad = grad_compress._flatten(grads, n_pods)
                    red, new_err = grad_compress.pod_reduce(
                        flat, err_pod[0], n_pods)
                    loss = jax.lax.pmean(loss, "pod")
                    metrics = jax.tree.map(
                        lambda m: jax.lax.pmean(m, "pod"), metrics)
                    grads = grad_compress._unflatten(red, pad, grads)
                    return loss, metrics, grads, new_err[None]

                bspecs = jax.tree.map(lambda _: P("pod"), batch)
                loss, metrics, grads, new_err = jax.shard_map(
                    body, mesh=self.mesh,
                    in_specs=(P(), bspecs, P("pod")),
                    out_specs=(P(), jax.tree.map(lambda _: P(), _metric_tree()),
                               jax.tree.map(lambda _: P(),
                                            abstract_params(self.spec_tree)),
                               P("pod")),
                    axis_names={"pod"}, check_vma=False,
                )(state["params"], batch, state["err"])
            elif cfg.microbatches > 1:
                n = cfg.microbatches
                dp_rule = self.rules["batch"]

                def split(x):
                    mb = x.reshape(n, x.shape[0] // n, *x.shape[1:])
                    # keep DP on the per-micro batch dim — without this the
                    # contiguous reshape puts the DP shards on the MICRO
                    # axis and every device replicates the whole batch
                    spec = jax.sharding.PartitionSpec(
                        None, dp_rule, *([None] * (mb.ndim - 2)))
                    return jax.lax.with_sharding_constraint(
                        mb, jax.sharding.NamedSharding(self.mesh, spec))

                mbs = jax.tree.map(split, batch)
                # grad accumulator lives in the ZeRO-1 (moment) layout:
                # the DP reduction becomes reduce-scatter and the f32
                # buffer is 1/dp per device
                acc_sh = param_shardings(
                    self.opt_specs["mu"], self.mesh,
                    dict(self.rules, __zero1__=self.rules["batch"]))
                g0 = jax.tree.map(
                    lambda p, s: jax.lax.with_sharding_constraint(
                        jnp.zeros(p.shape, jnp.float32), s),
                    state["params"], acc_sh)
                m0 = (jnp.float32(0), _metric_tree())

                def micro(carry, mb):
                    acc_g, (acc_l, acc_m) = carry
                    (l, m), g = grad_fn(state["params"], mb)
                    acc_g = jax.tree.map(
                        lambda a, b, s: jax.lax.with_sharding_constraint(
                            a + b.astype(jnp.float32) / n, s),
                        acc_g, g, acc_sh)
                    acc_m = jax.tree.map(lambda a, b: a + b / n, acc_m, m)
                    return (acc_g, (acc_l + l / n, acc_m)), None

                (grads, (loss, metrics)), _ = jax.lax.scan(
                    micro, (g0, m0), mbs)
                new_err = None
            else:
                (loss, metrics), grads = grad_fn(state["params"], batch)
                new_err = None

            new_params, new_opt, stats = self._update(
                grads, state["opt"], state["params"], lr)
            metrics = {**metrics, **stats, "loss": loss, "lr": lr}
            egress = self._egress(grads, loss, stats["grad_norm"])
            new_state = {"params": new_params, "opt": new_opt,
                         "step": state["step"] + 1}
            if new_err is not None:
                new_state["err"] = new_err
            return new_state, metrics, egress

        return train_step

    def jitted(self, shape_cfg=None):
        sh = self.state_shardings()
        bs = None
        if shape_cfg is not None:
            from repro.configs import input_specs
            bs = batch_shardings(self.mesh, self.rules,
                                 input_specs(self.model.cfg, shape_cfg))
        return jax.jit(self.step_fn(),
                       in_shardings=(sh, bs),
                       out_shardings=(sh, None, None),
                       donate_argnums=(0,))


def _metric_tree():
    return {"nll": 0.0, "z2": 0.0, "moe_lb": 0.0, "moe_z": 0.0}

