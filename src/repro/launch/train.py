"""Training CLI.

CPU (development):  PYTHONPATH=src python -m repro.launch.train \
    --arch qwen2_0_5b --smoke --steps 50
Mesh runs place params/opt-state with the same GSPMD shardings the dry-run
compiles (--mesh host uses a 1×1 mesh so the sharded code path is exercised
end-to-end on one chip).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import get_config
from repro.data import TokenBatcher, build_compressed_corpus, make_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import dp_axes, make_host_mesh
from repro.models import shard_ctx
from repro.models.model import build_model, param_specs
from repro.train import Trainer


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_0_5b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--compress-bits", type=int, default=0,
                    help="error-feedback bitplane gradient compression")
    ap.add_argument("--corpus-tokens", type=int, default=1 << 20)
    ap.add_argument("--compressed-corpus", action="store_true",
                    help="serve batches from the wavelet-matrix store")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", choices=["none", "host"], default="none")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    print(f"arch={cfg.name} family={cfg.family} "
          f"params={model_param_count(model):,}")

    toks = make_corpus(args.corpus_tokens, cfg.vocab_size, seed=args.seed)
    if args.compressed_corpus:
        corpus = build_compressed_corpus(toks, cfg.vocab_size)
        print(f"compressed corpus: {corpus.bits_per_token():.2f} bits/token "
              f"(raw 32)")
        batcher = TokenBatcher(corpus=corpus, batch=args.batch,
                               seq_len=args.seq, seed=args.seed)
    else:
        batcher = TokenBatcher(tokens=toks, batch=args.batch,
                               seq_len=args.seq, seed=args.seed)

    if args.mesh == "host":
        mesh = make_host_mesh()
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        shard_ctx.set_mesh_context(dp_axes(mesh), sizes)
        ctx = jax.set_mesh(mesh)
        ctx.__enter__()

    trainer = Trainer(
        model, batcher, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, seed=args.seed,
        log_every=args.log_every, grad_accum=args.grad_accum,
        base_lr=args.lr, warmup=args.warmup, total_steps=args.steps,
        compress_bits=args.compress_bits)
    if args.resume:
        start = trainer.maybe_resume()
        print(f"resumed at step {start}")
    trainer.run(args.steps)
    if trainer.history:
        first, last = trainer.history[0], trainer.history[-1]
        print(f"loss {first['loss']:.4f} -> {last['loss']:.4f} over "
              f"{last['step'] - trainer.history[0]['step'] + trainer.log_every} steps")


def model_param_count(model) -> int:
    import math
    sizes = [math.prod(s.shape) for s in
             jax.tree.leaves(model.abstract_params())]
    return sum(sizes)


if __name__ == "__main__":
    main()
