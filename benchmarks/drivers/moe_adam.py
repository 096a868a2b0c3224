"""Driver: sparse-attention expert-decoder pretraining, amp O2 +
FusedAdam (AdamW) with the global-norm clip folded into the update,
composed from the calls of ``examples/gpt/train_moe.py`` (its
``build_step``, ``build_optimizer`` and ``MAX_BUCKET_BYTES``, so the
bucket plan measured is the example's).

Weights, tokens and labels come from the seed; the window cycles a pool
of device-resident batches that all differ, their ids drawn from the
vocabulary's slice.  The tokens each held expert got are an array the
train step returns; the job fetches the newest when it drains (after
set-up, after the window), never inside a step.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp

from apex_tpu import amp
from apex_tpu.models.sparse_moe import SparseMoEDecoder

from benchmarks import counts, counts_moe, weights
from benchmarks.jobs import AmpTrainJob

POOL = 4


def _load_example(root):
    path = os.path.join(root, "examples", "gpt", "train_moe.py")
    spec = importlib.util.spec_from_file_location("bench_train_moe", path)
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
        layers, held = sizes["num_hidden_layers"], sizes["num_experts"]
        model = SparseMoEDecoder(
            vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
            num_heads=sizes["num_attention_heads"],
            num_kv_heads=sizes["num_key_value_heads"],
            head_dim=sizes["head_dim"], num_layers=layers,
            moe_ffn_hidden_size=sizes["moe_intermediate_size"],
            num_experts=sizes["router_num_experts"], experts_held=held,
            top_k=sizes["num_experts_per_tok"],
            index_heads=sizes["indexer_num_heads"],
            index_head_dim=sizes["indexer_head_dim"],
            index_topk=sizes["indexer_topk"],
            expert_offset=sizes["expert_offset"],
            norm_topk_prob=sizes["norm_topk_prob"],
            rms_norm_eps=sizes["rms_norm_eps"],
            rope_theta=float(sizes["rope_theta"]),
            index_loss_weight=sizes["index_loss_weight"],
            dtype=jnp.bfloat16)
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
        tokens_per_step = self.batch * self.seq
        shape = dict(
            hidden=sizes["hidden_size"], width=sizes["moe_intermediate_size"],
            top_k=sizes["num_experts_per_tok"], held=held,
            router_width=sizes["router_num_experts"], layers=layers)
        self.counts = {
            "step_flops": counts_moe.step_flops(
                self.batch, self.seq, heads=sizes["num_attention_heads"],
                kv_heads=sizes["num_key_value_heads"],
                head_dim=sizes["head_dim"],
                index_heads=sizes["indexer_num_heads"],
                index_head_dim=sizes["indexer_head_dim"],
                topk=sizes["indexer_topk"], vocab=vocab, **shape),
            "attention_flops": counts_moe.attention_flops(
                self.batch, sizes["num_attention_heads"], self.seq,
                sizes["head_dim"], sizes["indexer_topk"], layers),
            "expert_flops": counts_moe.expert_flops(tokens_per_step, **shape),
            "xent_bytes": counts_moe.xent_bytes(tokens_per_step, vocab),
            "optimizer_bytes": counts.optimizer_bytes(
                optimizer["algorithm"], n_params),
            "n_params": n_params,
            "expected_tokens_per_expert": (
                tokens_per_step * sizes["num_experts_per_tok"]
                / sizes["router_num_experts"]),
        }
        self.expert_counts = None
        self._finish_init()

    def next_batch(self, i):
        return self.pool[i % POOL]

    def forward_backward(self, batch):
        """-> (loss, grads, found_inf, clip_coef, aux); dispatches
        only."""
        return self.jstep(self.opt.params, self.amp_state.scaler, *batch)

    def step(self, i):
        """``AmpTrainJob.step`` with the example's additions (the train
        step's clip coefficient goes to the optimizer step, its expert
        counts are kept as the device array they are) and ONE step in
        flight, as the looped driver and for its reason: a step's
        outputs are allocated when it is launched, and a second step
        launched ahead does not fit beside the first."""
        if self.losses:
            self.wait(-1)
        with self.spans("input_wait"):
            batch = self.next_batch(i)
        with self.spans("dispatch_fwd_bwd"):
            loss, grads, found_inf, clip_coef, aux = self.forward_backward(
                batch)
        with self.spans("dispatch_optimizer"):
            self.opt.step(grads, found_inf=found_inf, clip_coef=clip_coef)
            self.amp_state = amp.update_scaler(self.amp_state, found_inf)
        self.losses.append(loss)
        self.found_infs.append(found_inf)
        self.expert_counts = aux["expert_counts"]
        return loss

    def drain(self):
        """Everything done; then the newest step's tokens per held
        expert (layers, held) come to the host: the fullest expert's
        load over the mean of all held experts' is ``moe_max_load``."""
        super().drain()
        if self.expert_counts is not None:
            got = jax.device_get(self.expert_counts)
            self.counts["expert_tokens"] = got.tolist()
            if got.sum() > 0:
                self.counts["moe_max_load"] = float(got.max() / got.mean())

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
        self.pool = self.jstep = self.expert_counts = None
        super().close()
