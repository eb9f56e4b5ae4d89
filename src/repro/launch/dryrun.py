"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST be the first import side effect: force 512 host placeholder devices
BEFORE jax initializes (single-pod mesh uses the first 256).
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import obs    # noqa: E402
from repro.configs.base import (ARCHITECTURES, SHAPES, get_config,  # noqa: E402
                                supports_shape)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import dp_axes, make_production_mesh  # noqa: E402
from repro.models.model import (abstract_cache, abstract_params,  # noqa: E402
                                build_model, cache_specs, param_specs)
from repro.optim.adamw import abstract_opt_state, adamw_update  # noqa: E402
from repro.optim.schedule import cosine_schedule  # noqa: E402

RESULTS_DEFAULT = Path("results/dryrun")

# --------------------------------------------------------------------------
# Step functions
# --------------------------------------------------------------------------


def make_train_step(model, grad_accum: int = 1):
    extras_keys = tuple(model.extras_shapes(1).keys())

    def grads_of(params, batch):
        tokens = batch["tokens"]
        extras = {k: batch[k] for k in extras_keys} or None
        if grad_accum == 1:
            return jax.value_and_grad(model.loss_fn)(params, tokens, extras)
        b = tokens.shape[0]
        mb = b // grad_accum
        mb_tok = tokens.reshape(grad_accum, mb, *tokens.shape[1:])
        mb_ext = jax.tree.map(
            lambda x: x.reshape(grad_accum, mb, *x.shape[1:]),
            extras) if extras else None

        def body(carry, xs):
            aloss, ag = carry
            ext = {k: xs[k] for k in extras_keys} or None
            loss, g = jax.value_and_grad(model.loss_fn)(
                params, xs["tokens"], ext)
            return (aloss + loss, jax.tree.map(jnp.add, ag, g)), None

        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                            params)
        xs = {"tokens": mb_tok, **(mb_ext or {})}
        (ls, gs), _ = jax.lax.scan(body, (jnp.float32(0), zero), xs)
        inv = 1.0 / grad_accum
        return ls * inv, jax.tree.map(lambda g: g * inv, gs)

    def train_step(params, opt_state, batch):
        loss, grads = grads_of(params, batch)
        lr = cosine_schedule(opt_state.step, 3e-4, 2000, 100_000)
        new_params, new_opt, metrics = adamw_update(params, grads, opt_state,
                                                    lr)
        return new_params, new_opt, {"loss": loss, **metrics}

    return train_step


# Microbatch counts for the train_4k cells: bounds per-device activation
# memory (grads accumulate across a lax.scan; collectives per optimizer
# step are unchanged). Chosen so peak HBM approaches the 16 GB v5e budget.
# capped at global_batch/data(=16): a microbatch below one example per
# data shard replicates activations and regresses memory.
GRAD_ACCUM = {
    "arctic_480b": 16, "dbrx_132b": 16, "llama_3_2_vision_90b": 16,
    "internlm2_20b": 8, "granite_3_8b": 8, "deepseek_7b": 8,
    "jamba_v0_1_52b": 8, "whisper_medium": 4, "qwen2_0_5b": 4,
    "mamba2_370m": 4,
}


def make_prefill_step(model):
    extras_keys = tuple(model.extras_shapes(1).keys())

    def prefill_step(params, batch):
        extras = {k: batch[k] for k in extras_keys} or None
        return model.prefill(params, batch["tokens"], extras)

    return prefill_step


def make_serve_step(model):
    def serve_step(params, tokens, cache, pos):
        return model.decode_step(params, tokens, cache, pos)

    return serve_step


# --------------------------------------------------------------------------
# Abstract inputs + shardings
# --------------------------------------------------------------------------

def input_specs(cfg, shape):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    model = build_model(cfg)
    b, s = shape.global_batch, shape.seq_len
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    extras = {k: jax.ShapeDtypeStruct(shp, bf16)
              for k, shp in model.extras_shapes(b).items()}
    if shape.kind == "train":
        return {"batch": {"tokens": jax.ShapeDtypeStruct((b, s + 1), i32),
                          **extras}}
    if shape.kind == "prefill":
        return {"batch": {"tokens": jax.ShapeDtypeStruct((b, s), i32),
                          **extras}}
    # decode: one token against a seq-length cache
    return {"tokens": jax.ShapeDtypeStruct((b, 1), i32),
            "cache": abstract_cache(cfg, b, s),
            "pos": jax.ShapeDtypeStruct((b,), i32)}


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_shardings(cfg, mesh, specs):
    from repro.models.model import fit_spec
    dp = dp_axes(mesh)
    sizes = mesh_axis_sizes(mesh)

    def spec_for(path, s):
        spec = P(*((dp,) + (None,) * (len(s.shape) - 1)))
        return NamedSharding(mesh, fit_spec(spec, s.shape, sizes))

    return jax.tree_util.tree_map_with_path(spec_for, specs)


# --------------------------------------------------------------------------
# Lower + compile + analyze one cell
# --------------------------------------------------------------------------

_COLLECTIVE_RE = re.compile(
    r"=\s*(?:\(?)([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "u16": 2,
                "s16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8}


def collective_bytes_per_device(hlo_text: str, body_trip_counts=None) -> dict:
    """Per-device collective traffic, parsed from post-SPMD HLO.

    Returns {op_kind: bytes} using each op's *result* shape (≈ bytes a device
    receives). Ops inside while-loop bodies (scan over blocks) are multiplied
    by the trip count inferred from the loop's induction-variable compare,
    parsed from the loop condition computations.
    """
    # map condition-computation name -> trip count (from "count < N" compares)
    trip_by_cond = {}
    for m in re.finditer(
            r"%?([\w.\-]+)\s*\([^)]*\)\s*->\s*pred\[\]\s*{(.*?)\n}\n",
            hlo_text, re.S):
        name, body = m.group(1), m.group(2)
        c = re.search(r"compare\([^)]*\),\s*direction=LT", body)
        k = re.search(r"constant\((\d+)\)", body)
        if c and k:
            trip_by_cond[name] = int(k.group(1))

    # map body-computation name -> trip count via while ops
    trip_by_comp = {}
    for m in re.finditer(
            r"while\([^)]*\),\s*condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)",
            hlo_text):
        cond, body = m.group(1), m.group(2)
        trip_by_comp[body] = trip_by_cond.get(cond, 1)

    totals = {}
    current_comp = None
    current_trip = 1
    for line in hlo_text.splitlines():
        header = re.match(r"%?([\w.\-]+)\s*\([^)]*\)\s*->", line)
        if header and "{" in line:
            current_comp = header.group(1)
            current_trip = trip_by_comp.get(current_comp, 1)
            continue
        mm = _COLLECTIVE_RE.search(line)
        if not mm:
            continue
        dtype, dims, kind = mm.groups()
        nbytes = _DTYPE_BYTES.get(dtype, 4)
        # XLA:CPU's FloatSupport promotes bf16 all-reduces to f32 (the
        # reducer is named "*promoted"); TPU reduces bf16 natively, so
        # count promoted ops at their true 2-byte width.
        if dtype == "f32" and "promoted" in line:
            nbytes //= 2
        numel = 1
        for d in dims.split(","):
            if d:
                numel *= int(d)
        totals[kind] = totals.get(kind, 0) + numel * nbytes * current_trip
    return totals


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save_hlo: Path | None = None) -> dict:
    from repro.models import shard_ctx
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    dp = dp_axes(mesh)
    sizes = mesh_axis_sizes(mesh)
    shard_ctx.set_mesh_context(dp, sizes)
    # decode steps use TP-only weight sharding (no per-token weight
    # gathers); train/prefill amortize FSDP gathers over the whole batch.
    pspecs = param_specs(cfg, sizes,
                         mode="decode" if shape.kind == "decode" else "train")
    pshard = _named(mesh, pspecs)
    specs = input_specs(cfg, shape)

    sw = obs.Stopwatch()
    mesh_ctx = jax.set_mesh(mesh)
    mesh_ctx.__enter__()
    if shape.kind == "train":
        step = make_train_step(model, grad_accum=GRAD_ACCUM.get(arch, 1))
        oshard = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            abstract_opt_state_specs(pspecs), is_leaf=lambda x: isinstance(x, P))
        bshard = batch_shardings(cfg, mesh, specs["batch"])
        fn = jax.jit(step,
                     in_shardings=(pshard, oshard, bshard),
                     out_shardings=(pshard, oshard, None),
                     donate_argnums=(0, 1))
        abstract_opt = abstract_opt_state(abstract_params(cfg))
        lowered = fn.lower(abstract_params(cfg), abstract_opt,
                           specs["batch"])
    elif shape.kind == "prefill":
        from repro.models.model import fit_spec
        step = make_prefill_step(model)
        bshard = batch_shardings(cfg, mesh, specs["batch"])
        logits_spec = fit_spec(P(dp, "model"),
                               (shape.global_batch, cfg.padded_vocab), sizes)
        fn = jax.jit(step, in_shardings=(pshard, bshard),
                     out_shardings=NamedSharding(mesh, logits_spec))
        lowered = fn.lower(abstract_params(cfg), specs["batch"])
    else:  # decode
        from repro.models.model import fit_spec
        step = make_serve_step(model)
        b, s = shape.global_batch, shape.seq_len
        cshard = _named(mesh, cache_specs(cfg, dp, b, s, sizes,
                                          shard_seq=True))
        tok_spec = fit_spec(P(dp, None), (b, 1), sizes)
        pos_spec = fit_spec(P(dp), (b,), sizes)
        logits_spec = fit_spec(P(dp, "model"), (b, cfg.padded_vocab), sizes)
        fn = jax.jit(
            step,
            in_shardings=(pshard, NamedSharding(mesh, tok_spec),
                          cshard, NamedSharding(mesh, pos_spec)),
            out_shardings=(NamedSharding(mesh, logits_spec), cshard),
            donate_argnums=(2,))
        lowered = fn.lower(abstract_params(cfg), specs["tokens"],
                           specs["cache"], specs["pos"])
    t_lower = sw.lap()

    analyzed = _analyze_compiled(lowered, save_hlo)
    mesh_ctx.__exit__(None, None, None)
    shard_ctx.clear_mesh_context()

    mem = analyzed["memory"]
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": mesh.devices.size,
        "ok": True,
        "lower_s": round(t_lower, 1), "compile_s": analyzed["compile_s"],
        "flops_per_device": analyzed["flops"],
        "bytes_accessed_per_device": analyzed["bytes_accessed"],
        "collective_bytes_per_device": analyzed["collective_bytes"],
        "memory": {
            **mem,
            "peak_bytes": ((mem["argument_bytes"] or 0)
                           + (mem["temp_bytes"] or 0)),
        },
    }
    return result


def abstract_opt_state_specs(pspecs):
    from repro.optim.adamw import AdamWState
    return AdamWState(m=pspecs, v=pspecs, step=P())


# --------------------------------------------------------------------------
# Range-analytics cell: lower + compile the batched serving path and the
# fused Pallas quantile kernel so HLO/cost analysis covers the new
# subsystem alongside the model cells.
# --------------------------------------------------------------------------

def _analyze_compiled(lowered, save_hlo: Path | None = None) -> dict:
    sw = obs.Stopwatch()
    compiled = lowered.compile()
    t_compile = sw.lap()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    if save_hlo:
        save_hlo.write_text(hlo)
    return {
        "ok": True, "compile_s": round(t_compile, 1),
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "collective_bytes": collective_bytes_per_device(hlo),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        },
    }


def run_analytics_cell(out_dir: Path, save_hlo: bool = False) -> dict:
    """Build a small analytics store and compile its serving programs:
    the four-op batched query path and the fused Pallas quantile kernel."""
    import numpy as np
    from repro.analytics import build_sharded_analytics
    from repro.data import make_corpus
    from repro.kernels.ops import wm_quantile_batch

    n, vocab, sb, B = 1 << 14, 1024, 12, 1024
    toks = np.asarray(make_corpus(n, vocab, seed=0), np.int64)
    eng = build_sharded_analytics(toks, vocab, shard_bits=sb)
    rng = np.random.default_rng(1)
    lo = jnp.asarray(rng.integers(0, n, B).astype(np.int32))
    hi = jnp.minimum(lo + jnp.asarray(
        rng.integers(1, n // 2, B).astype(np.int32)), n)
    k = jnp.asarray(rng.integers(0, n // 2, B).astype(np.int32))
    s0 = jnp.asarray(rng.integers(0, vocab, B).astype(np.int32))
    s1 = jnp.minimum(s0 + 32, vocab)

    serve = jax.jit(lambda e, a, b, c, x, y: (
        e.range_quantile(a, b, c), e.range_count(a, b, x, y),
        e.range_topk(a, b, 8), e.range_distinct(a, b)))
    sw = obs.Stopwatch()
    lowered = serve.lower(eng, lo, hi, k, s0, s1)
    cell_serve = _analyze_compiled(
        lowered, out_dir / "analytics__serve.hlo.txt" if save_hlo else None)
    cell_serve["lower_s"] = round(sw.lap(), 1)

    kern = jax.jit(lambda w, a, b, c: wm_quantile_batch(w, a, b, c))
    sw.lap()
    lowered = kern.lower(eng.shard(0), lo, hi, k)
    cell_kernel = _analyze_compiled(
        lowered,
        out_dir / "analytics__quantile_kernel.hlo.txt" if save_hlo else None)
    cell_kernel["lower_s"] = round(sw.lap(), 1)

    result = {
        "cell": "analytics", "ok": True,
        "n": n, "vocab": vocab, "batch": B,
        "num_shards": eng.num_shards,
        "serve_4op_batch": cell_serve,
        "fused_quantile_kernel": cell_kernel,
    }
    return result


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def cell_id(arch, shape_name, multi_pod):
    return f"{arch}__{shape_name}__{'2x16x16' if multi_pod else '16x16'}"


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--analytics", action="store_true",
                    help="also compile the range-analytics serving cell")
    ap.add_argument("--out", type=Path, default=RESULTS_DEFAULT)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()
    args.out.mkdir(parents=True, exist_ok=True)

    if args.analytics or args.all:
        out_file = args.out / "analytics__serving.json"
        if out_file.exists() and not args.force:
            print("=== analytics (cached) ===", flush=True)
        else:
            print("=== analytics ===", flush=True)
            try:
                res = run_analytics_cell(args.out, save_hlo=args.save_hlo)
                out_file.write_text(json.dumps(res, indent=1))
                print(json.dumps({k: res[k] for k in
                                  ("serve_4op_batch",
                                   "fused_quantile_kernel")}), flush=True)
            except Exception as e:  # noqa: BLE001
                out_file.write_text(json.dumps(
                    {"cell": "analytics", "ok": False,
                     "error": repr(e)[:2000]}))
                print(f"FAILED: {e!r}"[:500], flush=True)
        if args.analytics and not args.all and not args.arch:
            return

    if args.all:
        archs = list(ARCHITECTURES)
        shapes = list(SHAPES)
        meshes = [False, True]
    else:
        archs = [args.arch]
        shapes = [args.shape] if args.shape else list(SHAPES)
        meshes = [False, True] if args.both_meshes else [args.multi_pod]

    n_ok = n_fail = n_skip = 0
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                cid = cell_id(arch, shape_name, mp)
                out_file = args.out / f"{cid}.json"
                if out_file.exists() and not args.force:
                    n_skip += 1
                    continue
                if not supports_shape(arch, shape_name):
                    out_file.write_text(json.dumps(
                        {"arch": arch, "shape": shape_name,
                         "mesh": "2x16x16" if mp else "16x16",
                         "ok": False, "skipped": "full-attention arch: "
                         "long_500k requires sub-quadratic mixing"}))
                    n_skip += 1
                    continue
                print(f"=== {cid} ===", flush=True)
                try:
                    hlo_path = (args.out / f"{cid}.hlo.txt"
                                if args.save_hlo else None)
                    res = run_cell(arch, shape_name, mp, save_hlo=hlo_path)
                    out_file.write_text(json.dumps(res, indent=1))
                    print(json.dumps({k: res[k] for k in
                                      ("compile_s", "flops_per_device",
                                       "memory")}), flush=True)
                    n_ok += 1
                except Exception as e:  # noqa: BLE001
                    out_file.write_text(json.dumps(
                        {"arch": arch, "shape": shape_name,
                         "mesh": "2x16x16" if mp else "16x16",
                         "ok": False, "error": repr(e)[:2000]}))
                    print(f"FAILED: {e!r}"[:500], flush=True)
                    n_fail += 1
    print(f"done ok={n_ok} fail={n_fail} skip={n_skip}")


if __name__ == "__main__":
    main()
