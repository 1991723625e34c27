"""Cross-pod gradient compression (int8 all-gather over the DCN boundary).

At 1000+ node scale the cross-pod (DCN) gradient all-reduce dominates the
collective term of data-parallel training. We compress exactly that edge:
the loss/grad computation runs under ``shard_map`` that is MANUAL over the
``pod`` axis only (GSPMD still auto-shards data/model inside each pod); the
per-pod gradients are quantized to int8 with a per-leaf absmax scale,
all-gathered over ``pod`` (int8 on the wire: 8x fewer bytes than the
equivalent fp32 ring all-reduce at pod=2), dequantized and averaged.

This trades ~0.4% relative gradient error (absmax int8) for an 8x cut of
the DCN term; see EXPERIMENTS.md §Perf for the measured collective-term
delta on the most collective-bound cell.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_int8(g: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.max(jnp.abs(g.astype(jnp.float32))) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(g.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compressed_psum_pod(grads: Any, axis: str = "pod") -> Any:
    """Mean of gradient pytrees across ``axis`` with int8 wire format.

    Must run inside a shard_map manual over ``axis``.
    """
    n = jax.lax.axis_size(axis)

    def one(g):
        q, scale = quantize_int8(g)
        qs = jax.lax.all_gather(q, axis)              # (n, ...) int8 on wire
        ss = jax.lax.all_gather(scale, axis)          # (n,) f32 (negligible)
        deq = qs.astype(jnp.float32) * ss.reshape((n,) + (1,) * g.ndim)
        return jnp.mean(deq, axis=0).astype(g.dtype)

    return jax.tree.map(one, grads)


def make_compressed_grad_fn(loss_fn, mesh):
    """value_and_grad with int8 cross-pod reduction.

    ``loss_fn(params, batch) -> (loss, aux)``. Params are replicated across
    ``pod``; the batch's pod shard stays inside the pod. Inside the manual
    region GSPMD continues to auto-shard over (data, model).
    """
    if "pod" not in mesh.axis_names:
        # single pod: plain value_and_grad
        return jax.value_and_grad(loss_fn, has_aux=True)

    def local_grad(params, batch):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        grads = compressed_psum_pod(grads, "pod")
        loss = jax.lax.pmean(loss, "pod")
        aux = jax.tree.map(lambda a: jax.lax.pmean(a, "pod"), aux)
        return (loss, aux), grads

    smapped = jax.shard_map(
        local_grad, mesh=mesh,
        in_specs=(P(), P("pod")),      # params replicated, batch pod-sharded
        out_specs=((P(), P()), P()),
        axis_names={"pod"},
        check_vma=False,
    )

    def wrapped(params, batch):
        return smapped(params, batch)

    return wrapped
