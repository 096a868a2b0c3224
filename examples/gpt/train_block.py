"""GPT block training — BASELINE config 4: contrib.multihead_attn +
FusedAdam (reference recipe: GPT-2-style block with apex's fused
attention and Adam).

A causal transformer stack built directly from
contrib.multihead_attn.SelfMultiheadAttn (the reference's fused MHA
module) rather than the models/ zoo, trained with FusedAdam on
synthetic next-token data.

Usage:
    python examples/gpt/train_block.py [--steps 20] [--layers 4]
        [--hidden 512] [--heads 8] [--seq-len 512] [--batch-size 8]
"""

from __future__ import annotations

import argparse
import time

import flax.linen as nn
import jax
import jax.numpy as jnp

import apex_tpu
from apex_tpu import amp
from apex_tpu.offload import checkpoint_name
from apex_tpu.contrib.multihead_attn import SelfMultiheadAttn
from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.optimizers import FusedAdam


class Block(nn.Module):
    hidden: int
    heads: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        # pre-LN -> fused self-attention (norm-add variant) -> MLP
        attn = SelfMultiheadAttn(self.hidden, self.heads, bias=True,
                                 include_norm_add=True, name="attn")
        x, _ = attn(x, attn_mask="causal")
        h = FusedLayerNorm(self.hidden, name="ln2")(x)
        h = nn.Dense(4 * self.hidden, dtype=self.dtype,
                     param_dtype=jnp.float32, name="fc1")(h)
        # offload tag: no-op unless the block runs under an offload
        # remat policy (--offload-activations)
        h = checkpoint_name(jax.nn.gelu(h), "ffn_hidden")
        h = nn.Dense(self.hidden, dtype=self.dtype,
                     param_dtype=jnp.float32, name="fc2")(h)
        return x + h


class GPTBlocks(nn.Module):
    vocab: int
    hidden: int
    heads: int
    layers: int
    max_seq: int
    dtype: jnp.dtype = jnp.bfloat16
    offload_activations: bool = False

    @nn.compact
    def __call__(self, tokens):
        b, s = tokens.shape
        emb = self.param("embed", nn.initializers.normal(0.02),
                         (self.vocab, self.hidden), jnp.float32)
        pos = self.param("pos", nn.initializers.normal(0.02),
                         (self.max_seq, self.hidden), jnp.float32)
        x = emb[tokens] + pos[:s][None]
        x = jnp.transpose(x, (1, 0, 2)).astype(self.dtype)  # (s, b, h)
        blk_cls = Block
        if self.offload_activations:
            # remat each block; the tagged ffn hidden streams to pinned
            # host memory instead of being held or recomputed
            from apex_tpu.offload import offload_policy
            blk_cls = nn.remat(Block,
                               policy=offload_policy(("ffn_hidden",)))
        for i in range(self.layers):
            x = blk_cls(self.hidden, self.heads, self.dtype,
                        name=f"block{i}")(x)
        x = FusedLayerNorm(self.hidden, name="lnf")(x)
        return jnp.dot(x.astype(jnp.float32), emb.T)        # (s, b, V)


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--layers", type=int, default=0)
    p.add_argument("--hidden", type=int, default=0)
    p.add_argument("--heads", type=int, default=0)
    p.add_argument("--seq-len", type=int, default=0)
    p.add_argument("--batch-size", type=int, default=0)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--cpu", action="store_true",
                   help="the small CPU proxy the tests run: CPU "
                        "backend, small sizes unless given")
    p.add_argument("--offload-activations", action="store_true",
                   help="remat blocks with the ffn hidden streamed to "
                        "pinned host memory (apex_tpu.offload); "
                        "TPU-backend feature")
    return p.parse_args()


def main():
    args = parse_args()
    if args.cpu:
        from apex_tpu.platform import select_platform
        select_platform("cpu")
        print("--cpu: CPU backend, small proxy sizes (L2 h128 b2 s64 "
              "vocab 2048 unless given)")
    small = args.cpu
    layers = args.layers or (2 if small else 12)
    hidden = args.hidden or (128 if small else 768)
    heads = args.heads or (4 if small else 12)
    seq = args.seq_len or (64 if small else 512)
    batch = args.batch_size or (2 if small else 8)
    vocab = 2048 if small else 50257

    model = GPTBlocks(vocab, hidden, heads, layers, max_seq=max(seq, 128),
                      offload_activations=args.offload_activations)
    print(f"apex_tpu {apex_tpu.__version__}: gpt-block L{layers} "
          f"h{hidden} b{batch} s{seq} on {jax.default_backend()}")

    tokens0 = jnp.zeros((batch, seq), jnp.int32)
    params = model.init(jax.random.key(0), tokens0)["params"]
    params, amp_state = amp.initialize(params, opt_level="O2")
    opt = FusedAdam(params, lr=args.lr,
                    master_weights=bool(amp_state.properties.master_weights),
                    masters=amp_state.master_params)

    def loss_fn(p, tokens):
        logits = model.apply({"params": p}, tokens)     # (s, b, V)
        labels = jnp.roll(tokens, -1, axis=1).T         # (s, b)
        logp = jax.nn.log_softmax(logits)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return -jnp.mean(ll[:-1])

    @jax.jit
    def step(p, scaler, tokens):
        return amp.scaled_value_and_grad(loss_fn, scaler, p, tokens)

    # ONE fixed synthetic batch (see bert example: visible descent)
    tokens = jax.random.randint(jax.random.key(1), (batch, seq), 0, vocab)
    t0 = None
    for i in range(args.steps):
        loss, grads, found_inf = step(opt.params, amp_state.scaler,
                                      tokens)
        # branch-free overflow skip: the flag stays on device (the old
        # `if int(found_inf) == 0` gate synced the host every step)
        opt.step(grads, found_inf=found_inf)
        amp_state = amp.update_scaler(amp_state, found_inf)
        if i == 0:
            float(loss)
            t0 = time.time()
        if i % 5 == 0:
            print(f"step {i:3d} loss {float(loss):.4f}")
    jax.block_until_ready(opt.params)
    if t0 and args.steps > 1:
        dt = (time.time() - t0) / (args.steps - 1)
        print(f"step time {dt*1e3:.1f} ms  "
              f"({batch*seq/dt:.0f} tokens/sec)")


if __name__ == "__main__":
    main()
