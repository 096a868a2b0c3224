"""4D-parallel GPT training: data x pipeline x tensor (+sequence)
parallelism with interleaved-1F1B pipelining — the full apex_tpu
distributed stack in one user-facing script (reference scope:
apex/transformer used from Megatron-style pretraining loops).

    JAX_PLATFORMS=cpu python examples/gpt/train_4d.py \
        [--dp 2 --pp 2 --tp 2] [--virtual 2] [--steps 30]

Axes:
  dp — batch sharded over "data"; grads pmean'd
  pp — GPT stages over "pipe" via the differentiable interleaved-1F1B
       SPMD pipeline (``--virtual V`` chunks per stage; V=1 uses the
       non-interleaved 1F1B)
  tp — Column/RowParallel linears inside each stage over "model",
       vocab-parallel embedding + cross-entropy
  sp — activations sequence-sharded between TP regions (on iff tp>1)

Plus amp's dynamic loss scaler with the on-device ``lax.cond`` skip
and FusedAdam.  Runs on a virtual CPU mesh (dp*pp*tp devices) or a
real pod unchanged.
"""

from __future__ import annotations

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--pp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--virtual", type=int, default=2,
                    help="virtual chunks per pipe stage (1: plain 1F1B)")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches per step "
                         "(amp.scaled_value_and_grad's microbatches= "
                         "path — the scan-based accumulation with the "
                         "latched found_inf; replaces any hand-rolled "
                         "accumulation loop)")
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()
    n = args.dp * args.pp * args.tp

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}"
            ).strip()
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu import amp, comm
    from apex_tpu.models import GPTStage
    from apex_tpu.normalization import fused_layer_norm
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import tensor_parallel as tp
    from apex_tpu.transformer.pipeline_parallel import spmd

    dp, pp, tpsz, VCH = args.dp, args.pp, args.tp, args.virtual
    sp = tpsz > 1
    mesh = comm.initialize(data=dp, pipe=pp, model=tpsz)
    A_D, A_P, A_M = comm.AXIS_DATA, comm.AXIS_PIPE, comm.AXIS_MODEL
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} on "
          f"{jax.default_backend()}; {pp * VCH} virtual GPT stages")

    # tiny-but-real shapes (scale freely on hardware)
    V, H, NH, S = 128, 32, 4, 16
    MB, M = 2, 2
    B_local = MB * M
    s_loc = S // tpsz if sp else S

    embed = tp.VocabParallelEmbedding(V, H, name="embed")
    stage = GPTStage(H, NH, num_layers=1, sequence_parallel=sp)

    tokens = jnp.mod(jnp.arange(dp * B_local * S, dtype=jnp.int32) * 7,
                     V).reshape(dp * B_local, S)
    labels = jnp.roll(tokens, -1, axis=1)

    def stage_param_spec(path, leaf):
        name = "/".join(str(p.key) for p in path if hasattr(p, "key"))
        if "qkv" in name or "fc1" in name:
            inner = (P(None, A_M) if leaf.ndim == 2 else P(A_M))
        elif "proj/weight" in name or "fc2/weight" in name:
            inner = P(A_M, None)
        else:
            inner = P()
        return P(A_P, None, *inner)      # (pipe, chunk, ...)

    embed_spec = {"params": {"weight": P(A_M, None)}}
    lnf_spec = {"w": P(), "b": P()}

    def init_fn(key, tok):
        ev = embed.init(key, tok)
        x_dummy = jnp.zeros((s_loc, MB, H), jnp.float32)
        k2 = jax.random.fold_in(jax.random.fold_in(key, 7),
                                jax.lax.axis_index(A_P))
        svs = [stage.init(jax.random.fold_in(k2, c), x_dummy)
               for c in range(VCH)]
        sv = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *svs)
        sv = jax.tree_util.tree_map(lambda x: x[None], sv)
        lnf = {"w": jnp.ones((H,), jnp.float32),
               "b": jnp.zeros((H,), jnp.float32)}
        return ev, sv, lnf

    # param TREE structure from a tp=1 probe (collectives only trace
    # inside shard_map); shapes come from the real init
    comm.destroy()
    comm.initialize(data=n)
    probe = jax.eval_shape(
        GPTStage(H, NH, num_layers=1).init, jax.random.key(0),
        jnp.zeros((S, MB, H), jnp.float32))
    stage_specs = jax.tree_util.tree_map_with_path(stage_param_spec,
                                                   probe)
    comm.destroy()
    mesh = comm.initialize(data=dp, pipe=pp, model=tpsz)

    pspecs = (embed_spec, stage_specs, lnf_spec)
    params = jax.jit(comm.shard_map(
        init_fn, mesh, in_specs=(P(), P()), out_specs=pspecs))(
        jax.random.key(0), tokens[:B_local])

    # per-leaf state: opt_specs shards each state leaf like its param
    # (stages on pipe, embeddings on model) — a flat bucket would mix
    # axes, so the bucketed packing must stay off here
    opt = FusedAdam(params, lr=2e-3, fuse_buckets=False)
    opt_state = opt.opt_state
    scaler = amp.LossScaleState.create(2.0 ** 10)
    opt_specs = {"exp_avg": pspecs, "exp_avg_sq": pspecs}

    def train_step(params, opt_state, scaler, step, tok, lab):
        pipe_rank = jax.lax.axis_index(A_P)
        pp_size = comm.bound_axis_size(A_P)   # jax 0.4.x has no jax.lax.axis_size

        def loss_fn(params, tok, lab):
            ev, sv, lnf = params
            x = embed.apply(ev, tok)                  # (B, S, H)
            x = jnp.transpose(x, (1, 0, 2))           # (S, B, H)
            if sp:
                x = tp.scatter_to_sequence_parallel_region(x)
            # -1, not the global M: under --accum the loss sees a
            # microbatch slice of the local batch, so the pipeline
            # microbatch count adapts (B_micro // MB)
            ub = jnp.transpose(
                x.reshape(x.shape[0], -1, MB, H), (1, 0, 2, 3))
            y = spmd.spmd_pipeline_interleaved_1f1b_apply(
                lambda pv, xx: stage.apply(pv, xx),
                jax.tree_util.tree_map(lambda a: a[0], sv), ub)
            y = jnp.transpose(y, (1, 0, 2, 3)).reshape(
                x.shape[0], -1, H)
            # exactly ONE f-mapping syncs the head's partial d/dy
            # over tp ranks (see GPTModel): under SP the exit gather's
            # bwd reduce-scatter is it — final LN stays INSIDE the
            # region with copy_to'd params (grad psum); without SP, an
            # explicit copy_to after the LN
            if sp:
                wln = tp.copy_to_tensor_model_parallel_region(lnf["w"])
                bln = tp.copy_to_tensor_model_parallel_region(lnf["b"])
                y = fused_layer_norm(y, wln, bln)
                y = tp.gather_from_sequence_parallel_region(y)
            else:          # sp off => tpsz == 1 here: nothing to sync
                y = fused_layer_norm(y, lnf["w"], lnf["b"])
            logits = jnp.dot(y, ev["params"]["weight"].T,
                             preferred_element_type=jnp.float32)
            per_tok = tp.vocab_parallel_cross_entropy(
                logits, jnp.transpose(lab, (1, 0)))
            loss = jnp.mean(per_tok)
            # count the loss once across the pipe axis with the f/g
            # mapping (fwd psum, bwd identity) — a raw psum would
            # scale every gradient by pp in backward
            return tp.reduce_from_tensor_model_parallel_region(
                jnp.where(pipe_rank == pp_size - 1, loss, 0.0), A_P)

        # microbatches=N accumulates across a scan with the latched
        # found_inf (one bad microbatch skips the whole step); the
        # per-leaf layout is the right fit here — this step's state
        # shards per leaf across THREE mesh axes, which the packer
        # declines by design
        loss, grads, found_inf = amp.scaled_value_and_grad(
            loss_fn, scaler, params, tok, lab,
            microbatches=args.accum)
        gev, gsv, glnf = grads
        gev, glnf = (jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, A_P), t) for t in (gev, glnf))
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, A_D), (gev, gsv, glnf))
        for ax in (A_D, A_P, A_M):
            found_inf = jax.lax.pmax(found_inf, ax)
        params, opt_state = jax.lax.cond(
            found_inf == 0,
            lambda a: opt.functional_step(a[0], a[1], grads, step),
            lambda a: a, (params, opt_state))
        scaler = amp.update_state(scaler, found_inf)
        return params, opt_state, scaler, jax.lax.pmean(loss, A_D)

    step_jit = jax.jit(comm.shard_map(
        train_step, mesh,
        in_specs=(pspecs, opt_specs, P(), P(), P(A_D), P(A_D)),
        out_specs=(pspecs, opt_specs, P(), P())))

    loss0 = None
    for i in range(1, args.steps + 1):
        params, opt_state, scaler, loss = step_jit(
            params, opt_state, scaler, jnp.int32(i), tokens, labels)
        if i == 1:
            loss0 = float(loss)
        if i % 10 == 0:
            # 1-in-10-steps console echo, not a per-step sync
            print(f"step {i:3d} loss {float(loss):.4f} "   # apexlint: disable=APX102
                  f"scale {float(scaler.loss_scale):.0f}")   # apexlint: disable=APX102
    final = float(loss)
    assert final < loss0, (loss0, final)
    print(f"OK: loss {loss0:.4f} -> {final:.4f} "
          f"(dp={dp} pp={pp}x{VCH}chunks tp={tpsz} sp={sp})")


if __name__ == "__main__":
    main()
