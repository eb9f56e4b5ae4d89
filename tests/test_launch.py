"""Launch/dry-run machinery tests that run on a single CPU device.

The full 512-device lower+compile sweep is exercised by
``python -m repro.launch.dryrun --all`` (results under results/dryrun);
here we test the pure pieces: input specs, shape gating, HLO parsing, and a
real (1,1)-mesh jit with the production sharding rules.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (ARCHITECTURES, SHAPES, get_config,
                                smoke_shape, supports_shape)
from repro.launch.hlo_analysis import analyze_hlo

RESULTS = Path(__file__).resolve().parent.parent / "results" / "dryrun"


def test_shape_gating():
    assert supports_shape("mamba2_370m", "long_500k")
    assert supports_shape("jamba_v0_1_52b", "long_500k")
    for arch in ARCHITECTURES:
        if arch not in ("mamba2_370m", "jamba_v0_1_52b"):
            assert not supports_shape(arch, "long_500k"), arch
        for shp in ("train_4k", "prefill_32k", "decode_32k"):
            assert supports_shape(arch, shp)


@pytest.mark.parametrize("arch", ARCHITECTURES)
def test_input_specs_abstract(arch):
    """input_specs returns ShapeDtypeStructs only (no allocation)."""
    from repro.launch.dryrun import input_specs
    cfg = get_config(arch)
    for shape_name, shape in SHAPES.items():
        if not supports_shape(arch, shape_name):
            continue
        specs = input_specs(cfg, shape)
        for leaf in jax.tree.leaves(specs):
            assert isinstance(leaf, jax.ShapeDtypeStruct), (arch, shape_name)
        if shape.kind == "train":
            assert specs["batch"]["tokens"].shape == \
                (shape.global_batch, shape.seq_len + 1)
        elif shape.kind == "prefill":
            assert specs["batch"]["tokens"].shape == \
                (shape.global_batch, shape.seq_len)
        else:
            assert specs["tokens"].shape == (shape.global_batch, 1)


def test_hlo_collective_parsing():
    hlo = """
HloModule test
%cond (x: s32[]) -> pred[] {
  %c = s32[] constant(12)
  ROOT %r = pred[] compare(%x, %c), direction=LT
}
ENTRY %main (p: f32[128,256]) -> f32[128,256] {
  %ag = f32[128,256]{1,0} all-gather(%p), replica_groups={}
  %ar = bf16[64]{0} all-reduce(%x), to_apply=%add
  ROOT %out = f32[128,256] add(%ag, %ag)
}
"""
    from repro.launch.dryrun import collective_bytes_per_device
    got = collective_bytes_per_device(hlo)
    assert got["all-gather"] == 128 * 256 * 4
    assert got["all-reduce"] == 64 * 2


def test_hlo_analysis_dot_flops_and_trip_counts():
    hlo = """
HloModule m
%body (t: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %lhs = f32[8,32]{1,0} parameter(0)
  %rhs = f32[32,16]{1,0} constant(0)
  %d = f32[8,16]{1,0} dot(%lhs, %rhs), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %r = (s32[], f32[8,16]) tuple(%i, %d)
}
%cond (t: (s32[], f32[8,16])) -> pred[] {
  ROOT %p = pred[] constant(true)
}
ENTRY %main (p: f32[8,16]) -> f32[8,16] {
  %w = (s32[], f32[8,16]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %g = f32[8,16] get-tuple-element(%w), index=1
}
"""
    res = analyze_hlo(hlo)
    # dot: 2 * 8*16 * 32 = 8192 flops × 10 trips
    assert res["dot_flops_per_device"] == 8192 * 10


@pytest.mark.skipif(not RESULTS.exists(), reason="dry-run results absent")
def test_dryrun_results_all_cells_ok():
    """Every produced cell compiled (ok) or is an explicit long_500k skip."""
    files = list(RESULTS.glob("*.json"))
    assert len(files) >= 80, f"expected ≥80 cells, found {len(files)}"
    bad = []
    for f in files:
        rec = json.loads(f.read_text())
        if not rec.get("ok") and "skipped" not in rec:
            bad.append(f.name)
    assert not bad, bad


def test_host_mesh_train_step_with_production_shardings():
    """End-to-end jit with NamedShardings from the production rules on a
    (1,1) host mesh — same code path as the 256-chip launch."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import dp_axes, make_host_mesh
    from repro.models import shard_ctx
    from repro.models.model import build_model, param_specs
    from repro.train import init_train_state, make_train_step

    cfg = get_config("qwen2_0_5b", smoke=True)
    model = build_model(cfg)
    mesh = make_host_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    shard_ctx.set_mesh_context(dp_axes(mesh), sizes)
    try:
        with jax.set_mesh(mesh):
            specs = param_specs(cfg, sizes)
            state = init_train_state(model, 0)
            pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                  is_leaf=lambda x: isinstance(x, P))
            params = jax.tree.map(jax.device_put, state.params, pshard)
            state = type(state)(params=params, opt=state.opt, ef=state.ef)
            step = jax.jit(make_train_step(model, base_lr=1e-3))
            rng = np.random.default_rng(0)
            batch = {"tokens": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (2, 65)), jnp.int32)}
            new_state, metrics = step(state, batch)
            assert np.isfinite(float(metrics["loss"]))
    finally:
        shard_ctx.clear_mesh_context()
