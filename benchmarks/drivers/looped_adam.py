"""Driver: looped-decoder pretraining, amp O2 + FusedAdam (AdamW) with
the global-norm clip folded into the update, composed from the calls of
``examples/gpt/train_looped.py`` (its ``build_step``,
``build_optimizer`` and ``MAX_BUCKET_BYTES``, so the bucket plan
measured is the example's).

Weights, tokens and labels come from the seed; the window cycles a pool
of device-resident batches that all differ.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp

from apex_tpu import amp
from apex_tpu.models.looped import LoopedDecoder

from benchmarks import counts, counts_looped, weights
from benchmarks.jobs import AmpTrainJob

POOL = 4


def _load_example(root):
    path = os.path.join(root, "examples", "gpt", "train_looped.py")
    spec = importlib.util.spec_from_file_location("bench_train_looped", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Job(AmpTrainJob):
    programs = {"fwd_bwd": "step", "optimizer": "_full_step_flat"}
    first_update_field = "exp_avg"

    def __init__(self, *, root, sizes, optimizer, traffic, reference, seed,
                 devices):
        example = _load_example(root)
        self.seed, self.sizes = seed, sizes
        self.batch, self.seq = traffic["batch"], traffic["seq_len"]
        self.spec = reference.param_spec(sizes)
        self.first_update_scale = 1.0 / (1.0 - optimizer["beta1"])
        passes, layers = sizes["total_ut_steps"], sizes["num_hidden_layers"]
        model = LoopedDecoder(
            vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
            num_heads=sizes["num_attention_heads"], num_layers=layers,
            ffn_hidden_size=sizes["intermediate_size"], num_passes=passes,
            rms_norm_eps=sizes["rms_norm_eps"],
            rope_theta=float(sizes["rope_theta"]),
            entropy_weight=sizes["exit_entropy_weight"], dtype=jnp.bfloat16)
        params = weights.make(self.spec, seed)
        params, self.amp_state = amp.initialize(params, opt_level="O2")
        self.opt, self.amp_state = example.build_optimizer(
            params, self.amp_state, lr=optimizer["lr"],
            beta1=optimizer["beta1"], beta2=optimizer["beta2"],
            eps=optimizer["eps"], weight_decay=optimizer["weight_decay"])
        del params
        self.jstep = example.build_step(model, self.amp_state,
                                        optimizer["max_grad_norm"])

        vocab = sizes["vocab_size"]

        @jax.jit
        def make_pool(key):
            kt, kl = jax.random.split(key)
            shape = (POOL, self.batch, self.seq)
            return (jax.random.randint(kt, shape, 0, vocab),
                    jax.random.randint(kl, shape, 0, vocab))

        tokens, labels = make_pool(
            jax.random.fold_in(weights.seed_key(seed), 0x7a11))
        self.pool = [(tokens[i], labels[i]) for i in range(POOL)]
        n_params = sum(int(jnp.size(x)) for x in
                       jax.tree_util.tree_leaves(self.opt.params))
        self.units_per_step = float(self.batch * self.seq)
        self.counts = {
            "step_flops": counts_looped.looped_step_flops(
                self.batch, self.seq, sizes["hidden_size"], layers,
                sizes["num_attention_heads"], sizes["head_dim"],
                sizes["intermediate_size"], vocab, passes),
            "attention_flops": counts_looped.causal_attention_flops(
                self.batch, sizes["num_attention_heads"], self.seq,
                sizes["head_dim"], passes * layers),
            "xent_bytes": counts_looped.xent_bytes(
                self.batch * self.seq, vocab, passes),
            "optimizer_bytes": counts.optimizer_bytes(
                optimizer["algorithm"], n_params),
            "n_params": n_params,
            "loop_passes": passes,
            "layer_applications": passes * layers,
        }
        self._finish_init()

    def next_batch(self, i):
        return self.pool[i % POOL]

    def forward_backward(self, batch):
        """-> (loss, grads, found_inf, clip_coef); dispatches only."""
        return self.jstep(self.opt.params, self.amp_state.scaler, *batch)

    def step(self, i):
        """``AmpTrainJob.step`` with the example's one addition (the
        train step's clip coefficient goes to the optimizer step) and
        ONE step in flight: a step's outputs (gradients, new packed and
        unpacked parameters, new masters) are 4.07 GB, allocated when
        it is launched, and a second step launched ahead does not fit
        beside the first — the allocator then stalls at the brim and
        the memory peak is what fragmentation leaves, from run to run.
        Waiting for the previous train step leaves its optimizer and
        unpack programs (58 ms) queued, so the device never idles."""
        if self.losses:
            self.wait(-1)
        with self.spans("input_wait"):
            batch = self.next_batch(i)
        with self.spans("dispatch_fwd_bwd"):
            loss, grads, found_inf, clip_coef = self.forward_backward(batch)
        with self.spans("dispatch_optimizer"):
            self.opt.step(grads, found_inf=found_inf, clip_coef=clip_coef)
            self.amp_state = amp.update_scaler(self.amp_state, found_inf)
        self.losses.append(loss)
        self.found_infs.append(found_inf)
        return loss

    def reference_batches(self, n):
        return [self.pool[i % POOL] for i in range(n)]

    def compiled_programs(self):
        grads = jax.eval_shape(
            self.jstep, self.opt.params, self.amp_state.scaler,
            *self.pool[0])[1]
        return {
            "fwd_bwd": self.jstep.lower(
                self.opt.params, self.amp_state.scaler,
                *self.pool[0]).compile(),
            "optimizer": self.opt._jit_step.lower(
                *self.opt._step_args(grads, 1.0, jnp.int32(0))).compile(),
        }

    def close(self):
        self.pool = self.jstep = None
        super().close()
