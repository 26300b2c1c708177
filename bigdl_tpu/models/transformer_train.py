"""Transformer language-model training driver — the beyond-reference
long-context config (the reference's only LM is the LSTM PTBWordLM;
SURVEY.md §5 names sequence scaling as this framework's extension).

    python -m bigdl_tpu.models.transformer_train -f /path/to/ptb \\
        -b 8 --seqLen 512 --hiddenSize 256 --numLayers 4

Causal attention runs through the fused Pallas flash kernel on TPU
(auto-enabled; ops/pallas/flash_attention.py), so --seqLen scales to
multi-k tokens without materializing the (T, T) score matrix; across
chips the same model shards with tensor/sequence parallelism
(parallel/tensor_parallel.py TRANSFORMER_RULES, parallel/sequence.py).
Data handling mirrors ptb_train (PTB text files or a synthetic Zipf
corpus).
"""
from __future__ import annotations

import logging
import math
from typing import Optional

import jax.numpy as jnp

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.dataset.text import ptb_batchify
from bigdl_tpu.models.ptb_train import _load_corpus
from bigdl_tpu.models.train_utils import base_parser, configure, init_logging

logger = logging.getLogger("bigdl_tpu.train")


def _window_dataset(ids, batch: int, steps: int):
    xs, ys = ptb_batchify(ids, batch, steps)
    return DataSet.from_arrays(
        xs.reshape(-1, steps), ys.reshape(-1, steps), batch_size=batch)


def build(argv: Optional[list] = None):
    """Parse ``argv`` and build the run exactly as :func:`main` trains
    it; returns ``(configured Optimizer, validation DataSet)``."""
    p = base_parser("transformer_train", batch_size=8, max_epoch=5,
                    lr=1e-3)
    p.add_argument("--seqLen", type=int, default=512)
    p.add_argument("--vocabSize", type=int, default=10001)
    p.add_argument("--hiddenSize", type=int, default=256)
    p.add_argument("--numHeads", type=int, default=8)
    p.add_argument("--filterSize", type=int, default=1024)
    p.add_argument("--numLayers", type=int, default=4)
    p.add_argument("--dropout", type=float, default=0.1)
    p.add_argument("--gradClip", type=float, default=1.0)
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline-parallel stages (devices on the pipe "
                        "mesh axis; remaining devices become data)")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel degree (devices on the expert "
                        "mesh axis); implies a Switch-MoE FFN")
    p.add_argument("--moeExperts", type=int, default=0,
                   help="number of MoE experts (default 2*ep when --ep)")
    p.add_argument("--microBatches", type=int, default=0,
                   help="pipeline microbatches (default 2*pp)")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel degree (attention/FFN weights "
                        "over the 'model' mesh axis)")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel degree (sequence dim over "
                        "the 'seq' mesh axis)")
    args = p.parse_args(argv)
    if args.sp > 1 and (args.pp > 1 or args.ep > 1 or args.moeExperts):
        raise SystemExit("--sp (ring attention) composes with --tp/dp "
                         "only, not --pp/--ep")
    if args.tp > 1 and (args.ep > 1 or args.moeExperts):
        raise SystemExit("--tp composes with --pp/--sp/dp; tp x ep is "
                         "not wired yet")
    if args.sp > 1 and args.seqLen % args.sp:
        raise SystemExit(f"--seqLen {args.seqLen} must divide over "
                         f"--sp {args.sp} sequence shards")
    if args.ep > 1 and (args.moeExperts or 2 * args.ep) % args.ep:
        raise SystemExit(
            f"--moeExperts {args.moeExperts} must divide over --ep "
            f"{args.ep} expert shards (else the banks silently "
            "replicate while the mesh still spends devices on 'expert')")

    train_ids, valid_ids, vocab = _load_corpus(
        args.folder, args.vocabSize,
        args.syntheticSize or 16 * args.seqLen * args.batchSize)
    train_ds = _window_dataset(train_ids, args.batchSize, args.seqLen)
    val_ds = _window_dataset(valid_ids, args.batchSize, args.seqLen)

    mesh = None
    param_shardings = None
    distri_kwargs = {}
    if args.pp > 1:
        # pipeline parallelism: embed/trunk/unembed split over the pipe
        # axis, microbatched GPipe schedule, composed with dp on the
        # remaining devices (parallel/pipeline.py); --tp additionally
        # shards the stage weights over 'model' and --ep swaps the FFNs
        # for expert banks sharded over 'expert' — both ride GSPMD's
        # auto axes inside the manual pipe schedule
        from bigdl_tpu.parallel.mesh import (DATA_AXIS, EXPERT_AXIS,
                                             MeshConfig, make_mesh)
        from bigdl_tpu.parallel.pipeline import pipelined_transformer_lm

        mesh = make_mesh(MeshConfig(data=-1, pipe=args.pp,
                                    model=args.tp, expert=args.ep))
        # each data shard needs >=1 row per microbatch: M must divide
        # batch/data_parallel_degree
        per_shard = max(args.batchSize // mesh.shape[DATA_AXIS], 1)
        m_req = args.microBatches or 2 * args.pp
        m = next(d for d in range(min(m_req, per_shard), 0, -1)
                 if per_shard % d == 0)
        if m != m_req:
            logger.info("clamping pipeline microbatches %d -> %d "
                        "(batch %d over %d-way dp)", m_req, m,
                        args.batchSize, mesh.shape[DATA_AXIS])
        moe = args.moeExperts or (2 * args.ep if args.ep > 1 else 0)
        model = pipelined_transformer_lm(
            vocab_size=vocab, hidden_size=args.hiddenSize,
            num_heads=args.numHeads, filter_size=args.filterSize,
            num_layers=args.numLayers, mesh=mesh,
            num_microbatches=m,
            dropout=args.dropout, causal=True,
            data_axis=DATA_AXIS,
            moe_experts=moe,
        )
        from bigdl_tpu.parallel.tensor_parallel import TRANSFORMER_RULES

        param_shardings = model.param_shardings(
            mesh,
            tp_rules=TRANSFORMER_RULES if args.tp > 1 else None,
            expert_axis=EXPERT_AXIS if args.ep > 1 else None)
        # trunk params are pipe-sharded; keep optimizer state following
        # them rather than ZeRO-1's leading-dim-over-data layout
        distri_kwargs = {"zero1": False}
    elif args.ep > 1 or args.moeExperts:
        from bigdl_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(data=-1, expert=args.ep))
        model = nn.Transformer(
            vocab_size=vocab, hidden_size=args.hiddenSize,
            num_heads=args.numHeads, filter_size=args.filterSize,
            num_layers=args.numLayers, dropout=args.dropout, causal=True,
            moe_experts=args.moeExperts or 2 * args.ep, moe_mesh=mesh,
        )
        import jax

        from bigdl_tpu.parallel.expert import transformer_expert_shardings

        param_shardings = transformer_expert_shardings(
            mesh, jax.eval_shape(
                lambda: model.init_params(jax.random.PRNGKey(0))))
    else:
        model = nn.Transformer(
            vocab_size=vocab,
            hidden_size=args.hiddenSize,
            num_heads=args.numHeads,
            filter_size=args.filterSize,
            num_layers=args.numLayers,
            dropout=args.dropout,
            causal=True,
        )
        if args.tp > 1 or args.sp > 1:
            # tensor/sequence parallelism: attention/FFN weights shard
            # over 'model'; --sp shards the batch's sequence dim over
            # 'seq' AND switches the attention cores to ring attention
            # (parallel/sequence.py) — K/V rotate over ICI, no (T, T)
            # score matrix, long context scales with the ring
            import jax

            from bigdl_tpu.parallel.mesh import MeshConfig, make_mesh
            from bigdl_tpu.parallel.tensor_parallel import (
                TRANSFORMER_RULES, make_param_shardings)

            mesh = make_mesh(MeshConfig(data=-1, model=args.tp,
                                        seq=args.sp))
            if args.sp > 1:
                model = nn.Transformer(
                    vocab_size=vocab, hidden_size=args.hiddenSize,
                    num_heads=args.numHeads, filter_size=args.filterSize,
                    num_layers=args.numLayers, dropout=args.dropout,
                    causal=True, seq_mesh=mesh,
                )
                distri_kwargs = {"seq_dim": 1}
            tpl = jax.eval_shape(
                lambda: model.init_params(jax.random.PRNGKey(0)))
            param_shardings = make_param_shardings(
                mesh, tpl, TRANSFORMER_RULES)
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(logits=True))
    opt = optim.Optimizer.apply(
        model, train_ds, crit,
        end_trigger=optim.Trigger.max_epoch(args.maxEpoch),
        mesh=mesh, param_shardings=param_shardings, **distri_kwargs,
    )
    opt.set_optim_method(optim.Adam(args.learningRate))
    opt.set_gradient_clipping_by_l2_norm(args.gradClip)
    opt.set_validation(optim.Trigger.every_epoch(), val_ds,
                       [optim.Loss(crit)])
    opt.set_compute_dtype(jnp.bfloat16)
    return configure(opt, args), val_ds


def main(argv: Optional[list] = None) -> dict:
    init_logging()
    opt, val_ds = build(argv)
    opt.optimize()

    crit = opt.criterion
    results = optim.evaluate(
        opt.model, opt.final_params, opt.final_state, val_ds,
        [optim.Loss(crit)])
    val_loss = results[0][1].result()[0]
    ppl = math.exp(min(val_loss, 30.0))
    logger.info("validation loss %.4f perplexity %.2f", val_loss, ppl)
    return {"val_loss": val_loss, "perplexity": ppl}


if __name__ == "__main__":
    main()
