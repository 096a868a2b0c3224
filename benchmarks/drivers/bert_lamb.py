"""Driver: BERT masked-LM pretraining, amp O2 + FusedLAMB, composed from
the calls of ``examples/bert/pretrain_mlm.py`` (its ``build_step`` and
``MAX_BUCKET_BYTES``, so the bucket plan measured is the example's).

Weights, tokens and labels come from the seed; the window cycles a pool
of device-resident batches that all differ.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp

from apex_tpu import amp
from apex_tpu.models.bert import BertModel
from apex_tpu.optimizers import FusedLAMB

from benchmarks import counts, weights
from benchmarks.jobs import AmpTrainJob

POOL = 4


def _load_example(root):
    path = os.path.join(root, "examples", "bert", "pretrain_mlm.py")
    spec = importlib.util.spec_from_file_location("bench_pretrain_mlm", path)
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
        model = BertModel(
            vocab_size=sizes["padded_vocab_size"],
            hidden_size=sizes["hidden_size"],
            num_heads=sizes["num_attention_heads"],
            num_layers=sizes["num_hidden_layers"],
            max_seq_len=sizes["max_position_embeddings"],
            dtype=jnp.bfloat16)
        params = weights.make(self.spec, seed)
        params, self.amp_state = amp.initialize(params, opt_level="O2")
        self.opt = FusedLAMB(
            params, lr=optimizer["lr"], beta1=optimizer["beta1"],
            beta2=optimizer["beta2"], eps=optimizer["eps"],
            weight_decay=optimizer["weight_decay"],
            max_grad_norm=optimizer["max_grad_norm"],
            master_weights=True, masters=self.amp_state.master_params,
            max_bucket_bytes=example.MAX_BUCKET_BYTES)
        del params
        self.jstep = example.build_step(model, self.amp_state)

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
            "step_flops": counts.bert_step_flops(
                self.batch, self.seq, sizes["hidden_size"],
                sizes["num_hidden_layers"], sizes["num_attention_heads"],
                sizes["intermediate_size"], sizes["padded_vocab_size"]),
            "attention_flops": counts.attention_flops(
                self.batch, sizes["num_attention_heads"], self.seq,
                sizes["hidden_size"] // sizes["num_attention_heads"],
                sizes["num_hidden_layers"]),
            "optimizer_bytes": counts.optimizer_bytes("lamb", n_params),
            "n_params": n_params,
        }
        self._finish_init()

    def next_batch(self, i):
        return self.pool[i % POOL]

    def forward_backward(self, batch):
        return self.jstep(self.opt.params, self.amp_state.scaler, *batch)

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
