"""DCGAN with amp (port of the reference's examples/dcgan/main_amp.py —
the multiple-models/multiple-losses amp demo: two models, two optimizers,
independent loss scalers, exactly the `amp.initialize(models=[D, G],
optimizers=[optD, optG], num_losses=3)` pattern).

Synthetic image data; sizes tuned to smoke-run on CPU.

Usage: python examples/dcgan/main_amp.py [--steps 30] [--opt-level O1]
"""

from __future__ import annotations

import argparse

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu import amp
from apex_tpu.optimizers import FusedAdam


class Generator(nn.Module):
    ch: int = 16

    @nn.compact
    def __call__(self, z):
        # z (B, nz) -> (B, 16, 16, 3)
        h = nn.Dense(4 * 4 * self.ch * 4)(z)
        h = nn.relu(h.reshape(z.shape[0], 4, 4, self.ch * 4))
        h = nn.relu(nn.ConvTranspose(self.ch * 2, (4, 4),
                                     strides=(2, 2))(h))
        h = nn.ConvTranspose(3, (4, 4), strides=(2, 2))(h)
        return jnp.tanh(h)


class Discriminator(nn.Module):
    ch: int = 16

    @nn.compact
    def __call__(self, x):
        h = nn.leaky_relu(nn.Conv(self.ch, (4, 4), strides=(2, 2))(x),
                          0.2)
        h = nn.leaky_relu(nn.Conv(self.ch * 2, (4, 4),
                                  strides=(2, 2))(h), 0.2)
        return nn.Dense(1)(h.reshape(x.shape[0], -1))[:, 0]


def bce_logits(logit, target):
    return jnp.mean(jnp.maximum(logit, 0) - logit * target
                    + jnp.log1p(jnp.exp(-jnp.abs(logit))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--opt-level", default="O1")
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--nz", type=int, default=32)
    p.add_argument("--cpu", action="store_true",
                   help="force the CPU backend (see apex_tpu.platform)")
    args = p.parse_args()
    if args.cpu:
        from apex_tpu.platform import select_platform
        select_platform("cpu")

    netG, netD = Generator(), Discriminator()
    z0 = jnp.zeros((args.batch_size, args.nz))
    x0 = jnp.zeros((args.batch_size, 16, 16, 3))
    pG = netG.init(jax.random.PRNGKey(0), z0)["params"]
    pD = netD.init(jax.random.PRNGKey(1), x0)["params"]

    # reference pattern: multiple models/optimizers under one amp config,
    # D and G each driving their own loss scaler
    pG, ampG = amp.initialize(pG, opt_level=args.opt_level)
    pD, ampD = amp.initialize(pD, opt_level=args.opt_level)

    optG = FusedAdam(pG, lr=2e-4, beta1=0.5, beta2=0.999)
    optD = FusedAdam(pD, lr=2e-4, beta1=0.5, beta2=0.999)

    half = jnp.bfloat16 if args.opt_level != "O0" else jnp.float32
    key = jax.random.PRNGKey(2)

    def d_loss(pd, pg, z, real):
        fake = netG.apply({"params": pg}, z.astype(half))
        dr = netD.apply({"params": pd}, real.astype(half))
        df = netD.apply({"params": pd}, fake)
        return (bce_logits(dr.astype(jnp.float32), 1.0)
                + bce_logits(df.astype(jnp.float32), 0.0))

    def g_loss(pg, pd, z):
        fake = netG.apply({"params": pg}, z.astype(half))
        df = netD.apply({"params": pd}, fake)
        return bce_logits(df.astype(jnp.float32), 1.0)

    d_vg = jax.jit(lambda pd, pg, sc, z, x: amp.scaled_value_and_grad(
        d_loss, sc, pd, pg, z, x))
    g_vg = jax.jit(lambda pg, pd, sc, z: amp.scaled_value_and_grad(
        g_loss, sc, pg, pd, z))

    for step in range(args.steps):
        kz, kx, key = jax.random.split(key, 3)
        z = jax.random.normal(kz, (args.batch_size, args.nz))
        real = jnp.tanh(jax.random.normal(
            kx, (args.batch_size, 16, 16, 3)))
        lossD, gD, infD = d_vg(optD.params, optG.params, ampD.scaler,
                               z, real)
        if int(infD) == 0:
            optD.step(gD)
        ampD = amp.update_scaler(ampD, infD)
        lossG, gG, infG = g_vg(optG.params, optD.params, ampG.scaler, z)
        if int(infG) == 0:
            optG.step(gG)
        ampG = amp.update_scaler(ampG, infG)
        if step % 10 == 0:
            print(f"step {step:3d} lossD {float(lossD):.4f} "
                  f"lossG {float(lossG):.4f}")
    # reference checkpoint shape: one amp.state_dict() covering BOTH
    # scalers (num_losses=2 -> loss_scaler0/loss_scaler1)
    sd = amp.state_dict(ampD, ampG)
    ampD, ampG = amp.load_state_dict(sd, ampD, ampG)
    print(f"OK: D {float(lossD):.3f} G {float(lossG):.3f} "
          f"scalers {sorted(sd)}")


if __name__ == "__main__":
    main()
