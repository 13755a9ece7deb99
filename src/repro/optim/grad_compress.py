"""Compressed cross-pod gradient reduction with error feedback.

The paper's setting has a fast local network and a slow inter-environment
hop; its §6 proposes data reduction before the slow link. The multi-pod
training analogue: the in-pod gradient reduce rides fast ICI, the cross-pod
hop rides slow DCI. We compress exactly that hop:

  * train_step computes grads with the batch sharded over (`data` only) —
    pjit's autodiff all-reduces over `data` within each pod;
  * a shard_map (`pod_reduce`) then performs an int8
    block-quantized reduce-scatter + all-gather over the pod axis with
    per-(pod, block) scales and local error-feedback accumulation, so the
    bf16->int8 quantization error is re-injected next step (convergence-
    safe; standard EF-SGD result).

Wire bytes across pods: 2·N·1 B (int8 RS+AG) vs 2·N·4 B for an fp32 ring
all-reduce -> 4x reduction (+ scales, negligible at block=4096).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PyTree = Any
QBLOCK = 4096


def _quant_blocks(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: (n_blocks, QBLOCK) f32 -> (int8, scales f32)."""
    amax = jnp.max(jnp.abs(x), axis=1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def _flatten(tree: PyTree, n_pods: int = 1):
    leaves = jax.tree.leaves(tree)
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                            for l in leaves])
    pad = (-flat.size) % QBLOCK
    if pad:
        flat = jnp.pad(flat, (0, pad))
    flat2d = flat.reshape(-1, QBLOCK)
    # Pad rows to a multiple of n_pods so the ring reduce-scatter shards
    # evenly — must mirror `error_state`, which sizes the EF residual the
    # same way (g + e in body would otherwise shape-mismatch whenever
    # ceil(n/QBLOCK) % n_pods != 0).
    rpad = (-flat2d.shape[0]) % max(n_pods, 1)
    if rpad:
        flat2d = jnp.pad(flat2d, ((0, rpad), (0, 0)))
    return flat2d, pad + rpad * QBLOCK


def _unflatten(flat2d: jax.Array, pad: int, tree: PyTree) -> PyTree:
    flat = flat2d.reshape(-1)
    if pad:
        flat = flat[:-pad]
    leaves, treedef = jax.tree.flatten(tree)
    out, off = [], 0
    for l in leaves:
        out.append(flat[off:off + l.size].reshape(l.shape).astype(l.dtype))
        off += l.size
    return jax.tree.unflatten(treedef, out)


def pod_reduce(flat: jax.Array, err: jax.Array, n_pods: int):
    """int8 ring reduce-scatter + all-gather over `pod` with error feedback.

    Runs inside a shard_map that is manual over `pod`. `flat`, `err`: this
    pod's (n_blocks, QBLOCK) f32 gradient and residual; n_blocks must be a
    multiple of n_pods (`_flatten` and `error_state` pad to it). Returns
    (mean over pods, new residual).
    """
    g = flat + err                                    # error feedback in
    q, s = _quant_blocks(g)
    new_err = g - q.astype(jnp.float32) * s[:, None]  # residual out
    shard_rows = flat.shape[0] // n_pods
    mine = jax.lax.axis_index("pod")

    def rows_of(qr, sr):
        r = jax.lax.dynamic_slice_in_dim(qr, mine * shard_rows, shard_rows, 0)
        c = jax.lax.dynamic_slice_in_dim(sr, mine * shard_rows, shard_rows, 0)
        return r.astype(jnp.float32) * c[:, None]

    # reduce-scatter over pods: pod p owns rows [p*shard_rows, ...)
    acc = rows_of(q, s)
    qr, sr = q, s
    perm = [(i, (i + 1) % n_pods) for i in range(n_pods)]
    for _ in range(1, n_pods):
        qr = jax.lax.ppermute(qr, "pod", perm)        # int8 on the wire
        sr = jax.lax.ppermute(sr, "pod", perm)
        acc = acc + rows_of(qr, sr)
    acc = acc / n_pods
    # all-gather the reduced shards (int8 on the wire again)
    qa, sa = _quant_blocks(acc)
    q_all = jax.lax.all_gather(qa, "pod", axis=0, tiled=True)
    s_all = jax.lax.all_gather(sa, "pod", axis=0, tiled=True)
    return q_all.astype(jnp.float32) * s_all[:, None], new_err


def compressed_pod_allreduce(grads: PyTree, err: jax.Array, mesh):
    """Mean-reduce `grads` over the `pod` mesh axis with int8 compression +
    error feedback. `err`: f32 (n_blocks, QBLOCK) residual carried across
    steps (init zeros via `error_state`). Returns (reduced_grads, new_err).

    The shard_map is manual over every mesh axis with replicated specs:
    the body only talks over `pod`, and the other axes hold copies.
    """
    n_pods = mesh.shape["pod"]
    flat, pad = _flatten(grads, n_pods)
    fn = jax.shard_map(functools.partial(pod_reduce, n_pods=n_pods),
                       mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
                       check_vma=False)
    reduced, new_err = fn(flat, err)
    return _unflatten(reduced, pad, grads), new_err


def error_state(grads_abstract: PyTree, n_pods: int = 1) -> jax.ShapeDtypeStruct:
    n = sum(int(jnp.prod(jnp.asarray(l.shape)))
            for l in jax.tree.leaves(grads_abstract))
    n += (-n) % QBLOCK
    rows = n // QBLOCK
    rows += (-rows) % max(n_pods, 1)   # ring reduce-scatter row padding
    return jax.ShapeDtypeStruct((rows, QBLOCK), jnp.float32)
