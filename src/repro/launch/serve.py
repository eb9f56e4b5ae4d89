"""*Model*-serving CLI: batched prefill + autoregressive decode.

(The analytics *query* front-end — admission control, load shedding,
degradation ladder over the sharded wavelet-matrix engine — has its own
CLI in ``repro.launch.frontend``.)

PYTHONPATH=src python -m repro.launch.serve --arch qwen2_0_5b --smoke \
    --batch 4 --prompt-len 64 --decode-steps 32

``--ckpt-dir`` restores params from an integrity-verified checkpoint
(first run saves one). Model weights are not derivable from anything, so
a failed verification cannot be repaired — the CLI warns and falls back
to fresh init rather than serving silently corrupted weights.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import get_config
from repro.data import make_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model, zero_cache


def _params_with_checkpoint(model, seed: int, ckpt_dir: str | None):
    """Fresh-init params, replaced by a verified checkpoint restore when
    ``ckpt_dir`` holds one. Any restore failure — corruption, torn write,
    structure mismatch — warns and serves the fresh init; an empty
    directory is seeded with a checkpoint for the next run."""
    params = model.init(seed)
    if not ckpt_dir:
        return params, "init"
    from repro.checkpoint import (latest_step, restore_checkpoint,
                                  save_checkpoint)
    if latest_step(ckpt_dir) is None:
        save_checkpoint(ckpt_dir, 0, params,
                        extra_meta={"kind": "serve_params", "seed": seed})
        return params, "init (checkpoint saved)"
    try:
        restored, meta = restore_checkpoint(ckpt_dir, params)
        if meta.get("kind") not in (None, "serve_params"):
            raise ValueError(f"not a serve checkpoint "
                             f"(kind={meta.get('kind')!r})")
        return restored, "restore (verified)"
    except Exception as e:
        print(f"WARNING: checkpoint restore failed ({type(e).__name__}: "
              f"{e}) — serving fresh init")
        return params, "init (restore failed)"


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_0_5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--decode-steps", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=0,
                    help="cache length (default prompt+decode)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", type=str, default=None,
                    help="params checkpoint: verified restore when "
                         "present, fresh init (saved here) otherwise")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-dir", type=str, default=None,
                    help="export obs metrics snapshot + JSONL events here "
                         "(inspect with `python -m repro.launch.obs`)")
    ap.add_argument("--profile-dir", type=str, default=None,
                    help="capture a jax.profiler device trace of "
                         "prefill+decode into this directory")
    args = ap.parse_args()
    if args.metrics_dir:
        obs.configure(args.metrics_dir)
    obs.start_trace(args.profile_dir)

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    params, origin = _params_with_checkpoint(model, args.seed, args.ckpt_dir)
    print(f"params: {origin}")
    b = args.batch
    max_seq = args.max_seq or (args.prompt_len + args.decode_steps)

    toks = make_corpus(args.prompt_len * b * 4, cfg.vocab_size,
                       seed=args.seed)
    prompts = toks[:b * args.prompt_len].reshape(b, args.prompt_len)
    prompts = jnp.asarray(prompts, jnp.int32)
    extras = {k: jnp.zeros(shp, jnp.bfloat16)
              for k, shp in model.extras_shapes(b).items()} or None

    # ---- prefill: batch forward, last-position logits --------------------
    prefill = jax.jit(lambda p, t: model.prefill(p, t, extras))
    sw = obs.Stopwatch()
    logits = prefill(params, prompts)
    logits.block_until_ready()
    t_prefill = sw.lap()
    obs.histogram("serve.model.prefill.latency_s").observe(t_prefill)
    obs.gauge("serve.model.prefill.batch").set(b)
    print(f"prefill: {b}×{args.prompt_len} tokens in {t_prefill*1e3:.1f} ms "
          f"({b*args.prompt_len/t_prefill:.0f} tok/s)")

    # ---- warm the cache with the prompt (teacher-forced decode) ----------
    decode = jax.jit(model.decode_step)
    cache = zero_cache(cfg, b, max_seq)
    for i in range(args.prompt_len):
        _, cache = decode(params, prompts[:, i:i + 1], cache,
                          jnp.full((b,), i, jnp.int32))

    # ---- autoregressive decode -------------------------------------------
    key = jax.random.PRNGKey(args.seed)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    out = [tok]
    sw.lap()
    for s in range(args.decode_steps - 1):
        pos = jnp.full((b,), args.prompt_len + s, jnp.int32)
        logits, cache = decode(params, tok, cache, pos)
        if args.temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(
                sub, logits / args.temperature, axis=-1)[:, None]
        else:
            tok = jnp.argmax(logits, axis=-1)[:, None]
        tok = tok.astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(out[-1])
    t_dec = sw.lap()
    obs.histogram("serve.model.decode.latency_s").observe(t_dec)
    obs.gauge("serve.model.decode.batch").set(b)
    obs.gauge("serve.model.decode.qps").set(
        b * (args.decode_steps - 1) / max(t_dec, 1e-9))
    gen = np.concatenate([np.asarray(t) for t in out], axis=1)
    print(f"decode: {b}×{args.decode_steps} tokens in {t_dec*1e3:.1f} ms "
          f"({b*(args.decode_steps-1)/max(t_dec,1e-9):.0f} tok/s)")
    print("sample token ids:", gen[0, :16].tolist())
    obs.record_memory_gauges()
    if obs.stop_trace():
        print(f"device trace → {args.profile_dir}")
    if args.metrics_dir:
        obs.write_snapshot()
        print(f"metrics → {args.metrics_dir}")


if __name__ == "__main__":
    main()
