"""Per-leaf optimizer math shared by all fused optimizer facades.

Reference kernels: csrc/multi_tensor_{adam,sgd,lamb,novograd,adagrad}.cu
(SURVEY.md §2.4).  The bucketed step runs the same updates over flat
buffers (apex_tpu.ops.multi_tensor's ``flat_adam`` etc.); this file is
the update one leaf at a time, kept for two reasons.  It is the only
path for a tree the packer declines (sharded or non-float leaves,
``fuse_buckets=False``): each facade's ``_step_math`` maps these over
the pytree inside ONE jit, which XLA compiles to a handful of fused
elementwise loops.  And it is the independent oracle the tests compare
the flat path against (tests/test_bucketed_optimizers.py,
tests/test_multi_tensor.py).

All math accumulates in f32 regardless of storage dtype; master-weight
handling keeps f32 params alongside bf16 model params (reference O2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

tree_map = jax.tree_util.tree_map


def _f32(x):
    return x.astype(jnp.float32)


def global_grad_norm(grads) -> jax.Array:
    """Global L2 norm across a pytree (reference: multi_tensor_l2norm)."""
    leaves = jax.tree_util.tree_leaves(grads)
    if not leaves:
        return jnp.float32(0.0)
    return jnp.sqrt(sum(jnp.sum(_f32(g) ** 2) for g in leaves))


def adam_step(p, g, m, v, *, lr, beta1, beta2, eps, weight_decay, step,
              adam_w_mode=True, bias_correction=True, grad_scale=1.0):
    """One Adam/AdamW leaf update. Returns (p, m, v)."""
    pf = _f32(p)
    gf = _f32(g) / jnp.asarray(grad_scale, jnp.float32)
    b1 = jnp.asarray(beta1, jnp.float32)
    b2 = jnp.asarray(beta2, jnp.float32)
    wd = jnp.asarray(weight_decay, jnp.float32)
    if not adam_w_mode:
        gf = gf + wd * pf
    m = b1 * m + (1 - b1) * gf
    v = b2 * v + (1 - b2) * gf * gf
    if bias_correction:
        t = jnp.asarray(step, jnp.float32)
        c1r = 1.0 / (1.0 - b1 ** t)
        c2r = 1.0 / (1.0 - b2 ** t)
    else:
        c1r = c2r = jnp.float32(1.0)
    update = (m * c1r) / (jnp.sqrt(v * c2r) + jnp.asarray(eps, jnp.float32))
    if adam_w_mode:
        update = update + wd * pf
    return (pf - jnp.asarray(lr, jnp.float32) * update).astype(p.dtype), m, v


def sgd_step(p, g, buf, *, lr, momentum=0.0, dampening=0.0,
             weight_decay=0.0, nesterov=False, first_run=False,
             grad_scale=1.0):
    """One SGD leaf update (torch.optim.SGD semantics). Returns (p, buf)."""
    pf = _f32(p)
    gf = _f32(g) / jnp.asarray(grad_scale, jnp.float32)
    gf = gf + jnp.asarray(weight_decay, jnp.float32) * pf
    if momentum != 0.0:
        mom = jnp.asarray(momentum, jnp.float32)
        # first_run may be a traced bool: select instead of branching
        buf = jnp.where(
            first_run, gf,
            mom * buf + (1 - jnp.asarray(dampening, jnp.float32)) * gf)
        d = gf + mom * buf if nesterov else buf
    else:
        d = gf
    return (pf - jnp.asarray(lr, jnp.float32) * d).astype(p.dtype), buf


def lamb_step(p, g, m, v, *, lr, beta1, beta2, eps, weight_decay, step,
              bias_correction=True, grad_scale=1.0, clip_coeff=1.0,
              use_nvlamb=False):
    """One LAMB leaf update (reference: multi_tensor_lamb stage1+stage2).

    ``clip_coeff`` is the precomputed global-grad-norm clip factor
    (stage-1 side input in the reference).  Trust ratio is per tensor:
    ||p|| / ||update||, guarded to 1 when either norm is 0.
    """
    pf = _f32(p)
    gf = _f32(g) * (jnp.asarray(clip_coeff, jnp.float32) /
                    jnp.asarray(grad_scale, jnp.float32))
    b1 = jnp.asarray(beta1, jnp.float32)
    b2 = jnp.asarray(beta2, jnp.float32)
    wd = jnp.asarray(weight_decay, jnp.float32)
    m = b1 * m + (1 - b1) * gf
    v = b2 * v + (1 - b2) * gf * gf
    if bias_correction:
        t = jnp.asarray(step, jnp.float32)
        c1r = 1.0 / (1.0 - b1 ** t)
        c2r = 1.0 / (1.0 - b2 ** t)
    else:
        c1r = c2r = jnp.float32(1.0)
    update = (m * c1r) / (jnp.sqrt(v * c2r) + jnp.asarray(eps, jnp.float32))
    update = update + wd * pf
    p_norm = jnp.sqrt(jnp.sum(pf * pf))
    u_norm = jnp.sqrt(jnp.sum(update * update))
    trust = jnp.where((p_norm > 0) & (u_norm > 0), p_norm / u_norm, 1.0)
    if not use_nvlamb:
        # standard LAMB exempts decay-free tensors from layer adaptation;
        # NVLAMB (use_nvlamb=True) applies the trust ratio to every layer
        trust = jnp.where(wd == 0.0, jnp.float32(1.0), trust)
    return (pf - jnp.asarray(lr, jnp.float32) * trust * update
            ).astype(p.dtype), m, v


def novograd_step(p, g, m, v_scalar, *, lr, beta1, beta2, eps,
                  weight_decay, first_run=False, grad_averaging=True,
                  grad_scale=1.0, init_zero=False,
                  reg_inside_moment=False):
    """One NovoGrad leaf update (reference: multi_tensor_novograd.cu).

    ``v_scalar`` is the per-TENSOR second moment (a scalar).
    ``init_zero``: start v at 0 (first step uses (1-b2)*||g||^2) instead
    of seeding with the first gradient norm.  ``reg_inside_moment``:
    fold weight decay into the normalized gradient before the
    first-moment EMA; otherwise decay is applied outside the moment."""
    pf = _f32(p)
    gf = _f32(g) / jnp.asarray(grad_scale, jnp.float32)
    b1 = jnp.asarray(beta1, jnp.float32)
    b2 = jnp.asarray(beta2, jnp.float32)
    wd = jnp.asarray(weight_decay, jnp.float32)
    g_norm_sq = jnp.sum(gf * gf)
    if init_zero:
        v_scalar = jnp.where(first_run, (1 - b2) * g_norm_sq,
                             b2 * v_scalar + (1 - b2) * g_norm_sq)
    else:
        v_scalar = jnp.where(first_run, g_norm_sq,
                             b2 * v_scalar + (1 - b2) * g_norm_sq)
    denom = jnp.sqrt(v_scalar) + jnp.asarray(eps, jnp.float32)
    gn = gf / denom
    if reg_inside_moment:
        gn = gn + wd * pf
    coeff = (1 - b1) if grad_averaging else jnp.float32(1.0)
    m = jnp.where(first_run, gn, b1 * m + coeff * gn)
    update = m if reg_inside_moment else m + wd * pf
    return (pf - jnp.asarray(lr, jnp.float32) * update
            ).astype(p.dtype), m, v_scalar


def adagrad_step(p, g, h, *, lr, eps, weight_decay, grad_scale=1.0):
    """One Adagrad leaf update (reference: multi_tensor_adagrad.cu)."""
    pf = _f32(p)
    gf = _f32(g) / jnp.asarray(grad_scale, jnp.float32)
    gf = gf + jnp.asarray(weight_decay, jnp.float32) * pf
    h = h + gf * gf
    return (pf - jnp.asarray(lr, jnp.float32) * gf /
            (jnp.sqrt(h) + jnp.asarray(eps, jnp.float32))).astype(p.dtype), h
