"""Launch-layer units: sharding rules, HLO stats parsing, shapes config."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.shapes import SHAPES, long_context_variant
from repro.launch import hlo_stats
from repro.launch.mesh import cache_pspecs, param_pspecs


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


MESH = FakeMesh({"data": 16, "model": 16})


def test_param_rules_basic():
    params = {
        "embed": {"embedding": sds((256000, 4096))},
        "layer": {"mixer": {"q": {"kernel": sds((4096, 4096))},
                            "o": {"kernel": sds((4096, 4096))}},
                  "mlp": {"up": {"kernel": sds((4096, 16384))}},
                  "pre_norm": {"norm_scale": sds((4096,))}},
    }
    specs = param_pspecs(params, mesh=MESH)
    assert specs["embed"]["embedding"] == P("model", None)
    assert specs["layer"]["mixer"]["q"]["kernel"] == P(None, "model")
    assert specs["layer"]["mixer"]["o"]["kernel"] == P("model", None)
    assert specs["layer"]["pre_norm"]["norm_scale"] == P()


def test_param_rules_divisibility_fallback():
    # granite: 40 experts don't divide model=16 -> axis moves to d_ff dim
    params = {"mlp": {"experts": {"up": sds((40, 1536, 512))}}}
    specs = param_pspecs(params, mesh=MESH)
    # model axis moved off the non-divisible expert dim (40) onto d_model
    assert specs["mlp"]["experts"]["up"] == P(None, "model", None)
    # mamba vocab 50280 doesn't divide -> model moves to d_model dim
    params = {"embed": {"embedding": sds((50280, 2560))}}
    specs = param_pspecs(params, mesh=MESH)
    assert specs["embed"]["embedding"] == P(None, "model")


def test_param_rules_scanned_leading_dim():
    params = {"blocks": [{"mixer": {"q": {"kernel": sds((28, 4096, 4096))}}}]}
    specs = param_pspecs(params, mesh=MESH)
    assert specs["blocks"][0]["mixer"]["q"]["kernel"] == P(None, None, "model")


def test_param_rules_fsdp():
    params = {"layer": {"mlp": {"up": {"kernel": sds((4096, 16384))}}}}
    specs = param_pspecs(params, fsdp=True, mesh=MESH)
    assert specs["layer"]["mlp"]["up"]["kernel"] == P("data", "model")


def test_cache_rules():
    cache = {
        "prefix": [{"k": sds((128, 32768, 16, 128), jnp.bfloat16)}],
        "blocks": {"k": sds((28, 128, 32768, 8, 128), jnp.bfloat16)},
    }
    specs = cache_pspecs(cache, ("data",), MESH)
    # 16 kv heads divide -> heads sharded
    assert specs["prefix"][0]["k"] == P(("data",), None, "model", None)
    # 8 kv heads don't -> head_dim sharded; scanned leading dim unsharded
    assert specs["blocks"]["k"] == P(None, ("data",), None, None, "model")


def test_cache_rules_batch_one():
    cache = {"prefix": [{"k": sds((1, 32768, 16, 128), jnp.bfloat16)}]}
    specs = cache_pspecs(cache, ("data",), MESH)
    assert specs["prefix"][0]["k"] == P(None, None, "model", None)


def test_hlo_stats_parsing():
    text = """
      %ag = bf16[16,4096]{1,0} all-gather(%x), replica_groups=...
      %ar.1 = f32[1024]{0} all-reduce-start(%y), to_apply=%add
      %rs = bf16[256,16]{1,0} reduce-scatter(%z)
      %cp = f32[8,8]{1,0} collective-permute(%w)
      ROOT %t = (f32[8]{0}) tuple(%cp)
    """
    st = hlo_stats.collective_stats(text)
    assert st["all-gather"]["count"] == 1
    assert st["all-gather"]["bytes"] == 16 * 4096 * 2
    assert st["all-reduce"]["count"] == 1
    assert st["all-reduce"]["bytes"] == 1024 * 4
    assert st["reduce-scatter"]["bytes"] == 256 * 16 * 2
    assert st["by_dtype"]["f32"] == 1024 * 4 + 64 * 4
    assert st["total_count"] == 4


def test_bucket_audit_reports_dropped_ops():
    """Regression (ISSUE 10): ops under the min_bytes floor used to vanish
    from the audit silently -- a sub-KiB fp32 bucket of a small model was
    simply missing. They must now be surfaced in the ``dropped`` entry."""
    text = """
      %ar0 = f32[65536]{0} all-reduce(%a), replica_groups=...
      %ar1 = f32[64]{0} all-reduce(%b), replica_groups=...
      %ar2 = f32[1]{0} all-reduce(%c), replica_groups=...
    """
    audit = hlo_stats.bucket_audit(text, min_bytes=1024)
    assert audit["num_exchanges"] == 1
    assert audit["dropped"]["count"] == 2
    assert audit["dropped"]["bytes"] == 64 * 4 + 4
    assert audit["dropped"]["min_bytes"] == 1024
    assert audit["dropped"]["by_kind"]["all-reduce"]["count"] == 2
    # floor 0 drops nothing
    audit0 = hlo_stats.bucket_audit(text, min_bytes=0)
    assert audit0["num_exchanges"] == 3
    assert audit0["dropped"]["count"] == 0


def test_dryrun_audit_floor_derived_from_schedule():
    """The dry-run's audit floor tracks the resolved schedule's smallest
    exchange instead of hardcoding 1 KiB (ISSUE 10 bugfix)."""
    from repro.launch.dryrun import _audit_floor
    # fp32 group of a small model: 272-byte exchange must stay in view
    assert _audit_floor({"min_exchange_bytes": 272}) == 272
    # huge buckets: clamp to the historical 1 KiB (still drops loss psums)
    assert _audit_floor({"min_exchange_bytes": 4 << 20}) == 1024
    # degenerate tiny exchange: never below 16 B (scalar metric psums)
    assert _audit_floor({"min_exchange_bytes": 4}) == 16
    # FSDP: no manual schedule -> historical floor
    assert _audit_floor({}) == 1024
    assert _audit_floor({"min_exchange_bytes": None}) == 1024


def test_shapes_and_long_variant():
    assert SHAPES["train_4k"].step == "train"
    assert SHAPES["long_500k"].step == "decode"
    from repro.configs import registry
    m = long_context_variant(registry.get("mamba2-2.7b"))
    assert m.pattern == ("ssd",)          # ssm untouched
    g = long_context_variant(registry.get("gemma-7b"))
    assert g.pattern == ("local",) and g.window == 32768


def test_dryrun_results_complete():
    """All 80 combos exist on disk and lowered successfully."""
    import glob
    import json
    files = glob.glob("experiments/dryrun/*.json")
    if len(files) < 80:
        pytest.skip("dry-run sweep artifacts not generated in this checkout")
    assert len(files) == 80
    for f in files:
        with open(f) as fh:
            rec = json.load(fh)
        assert rec["cost"]["flops"] is not None
        assert rec["collectives"]["total_count"] >= 0


def test_collective_counts_include_tuple_and_async_ops():
    hlo = """
  %ar = (f32[8]{0}, bf16[4]{0}) all-reduce(f32[8]{0} %a, bf16[4]{0} %b), replica_groups={{0,1}}
  %ags = (f32[4]{0}, f32[8]{0}) all-gather-start(f32[4]{0} %c), dimensions={0}
  %agd = f32[8]{0} all-gather-done((f32[4]{0}, f32[8]{0}) %ags)
  %rs = f32[2]{0} reduce-scatter(f32[8]{0} %d), dimensions={0}
"""
    assert hlo_stats.collective_counts(hlo) == {
        "all-reduce": 1, "all-gather": 1, "reduce-scatter": 1,
        "all-to-all": 0, "collective-permute": 0}


def test_compile_cache_dir(monkeypatch):
    """The cache goes where JAX_COMPILATION_CACHE_DIR says, else to one
    fixed, git-ignored directory in the checkout."""
    import os
    from repro.launch import train
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        train.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = train.use_compile_cache()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
