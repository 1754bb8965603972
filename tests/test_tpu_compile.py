"""Compile rehearsals for a described TPU v5e 2x2 host: no chip needed.

The TPU compiler is installed with libtpu, and compiles for a topology that
is described rather than attached. These tests compile the main path's
kernels and the ResNet-50 train step at real widths with it, so Mosaic
refusals, programs that do not fit the chip's memory and collectives that
do not lower show up here instead of on the chip. Nothing runs: inputs are
``ShapeDtypeStruct``s placed on the described devices.

The topology is described inside a module-scope fixture, never while a
module is imported: only one process at a time may load libtpu, and every
xdist worker imports every test file. Keep all such tests in this file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import registry
from repro.core.grad_sync import GradSyncConfig, sync_tree
from repro.core.topology import TorusGrid
from repro.kernels import ops
from repro.launch import hlo_stats
from repro.launch.train import device_mesh, resnet_job
from repro.models import resnet
from repro.train.trainer import make_train_step

V5E_HBM_BYTES = 16 * 10**9          # one v5e chip (Google Cloud, "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip compile is written to a persistent cache but can
        # never be read back without the chip: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# ------------------------------------------------------------- kernels --

@pytest.mark.parametrize("shape", [(7, 7, 3, 64), (3, 3, 512, 512)],
                         ids=["stem_conv", "stage4_conv2"])
def test_lars_update_compiles(one_chip, shape):
    """ResNet-50 leaves: the 9408-element stem pads its last row of 128."""
    x = _sds(shape, jnp.float32, one_chip)

    def step(p, g, v):
        return ops.lars_update(p, g, v, lr=0.2, mom=0.9, eta=0.01,
                               weight_decay=5e-5, eps=1e-6, interpret=False)

    assert "tpu_custom_call" in _compiled_text(step, x, x, x)


@pytest.mark.parametrize("rows,vocab,dtype", [
    (256, 1000, jnp.float32),                                   # ResNet head
    (256, registry.get("qwen3-1.7b").vocab, jnp.bfloat16),      # LM head
    (3, 300, jnp.bfloat16),                # rows and vocab padded to tiles
], ids=["resnet50", "qwen3-1.7b", "padded"])
def test_ls_xent_compiles(one_chip, rows, vocab, dtype):
    logits = _sds((rows, vocab), dtype, one_chip)
    labels = _sds((rows,), jnp.int32, one_chip)

    def loss(x, y):
        return ops.ls_xent(x, y, smoothing=0.1, interpret=False)

    assert "tpu_custom_call" in _compiled_text(loss, logits, labels)


def test_flash_attention_compiles(one_chip):
    """qwen3-1.7b's attention width: 16 query heads over 8 kv heads, 128
    wide, at a 2048-token sequence."""
    cfg = registry.get("qwen3-1.7b")
    q = _sds((1, 2048, cfg.n_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _sds((1, 2048, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16,
              one_chip)

    def attn(q, k, v):
        return ops.flash_attention(q, k, v, interpret=False)

    assert "tpu_custom_call" in _compiled_text(attn, q, kv, kv)


# ------------------------------------------------------ gradient exchange --

@pytest.mark.parametrize("strategy", ["psum", "torus2d"])
def test_model_sharded_bf16_exchange_compiles(topo, strategy):
    """bf16 exchange of a ``P(None, "model")`` gradient under a
    partial-manual shard_map (data manual, model auto). The CPU backend
    aborts on the bf16 all-reduce of this program; the TPU compiler takes
    both strategies. The mesh's axes are Auto: under ``jax.make_mesh``'s
    Explicit axes torus2d's reduce-scatter fails to trace."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    grid = TorusGrid(h_axes=("data",), v_axes=())
    cfg = GradSyncConfig(strategy=strategy, fuse=False,
                         comm_dtype=jnp.bfloat16, small_leaf_threshold=1)

    def step(w, x):
        g = jax.grad(lambda w, x: jnp.sum(jnp.tanh(x @ w)))(w, x)
        return sync_tree(g, grid, cfg)

    smapped = jax.shard_map(step, mesh=mesh, in_specs=(P(), P("data")),
                            out_specs=P(), axis_names=frozenset({"data"}),
                            check_vma=False)
    w = _sds((64, 64), jnp.float32, NamedSharding(mesh, P(None, "model")))
    x = _sds((8, 64), jnp.float32, NamedSharding(mesh, P("data")))
    counts = hlo_stats.collective_counts(_compiled_text(smapped, w, x))
    assert counts["all-reduce"] >= 1, counts


# ------------------------------------------------------------ train step --

def test_resnet50_train_step_compiles_on_2x2(topo, record_property):
    """chip_smoke.py's four-chip step: ResNet-50 at 224 px, 1000 classes,
    256 images per chip, torus2d, on the mesh ``device_mesh`` builds."""
    mesh = device_mesh(topo.devices)
    assert mesh.devices.shape == (2, 2)
    per_chip = 256
    trainer, state, _ = resnet_job(resnet.ResNetConfig.resnet50(),
                                   per_chip_batches=(per_chip,),
                                   strategy="torus2d", mesh=mesh)
    rep = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P(trainer.dp_axes))
    gb = per_chip * mesh.devices.size
    state = jax.tree.map(lambda a: _sds(a.shape, a.dtype, rep), state)
    batch = (_sds((gb, 224, 224, 3), jnp.float32, data),
             _sds((gb,), jnp.int32, data))
    scalar = _sds((), jnp.float32, rep)
    step = make_train_step(trainer.loss_fn, trainer.mesh, trainer.dp_axes,
                           trainer.cfg)
    compiled = step.lower(state, batch, scalar, scalar).compile()

    mem = compiled.memory_analysis()
    per_device = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                  + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    counts = hlo_stats.collective_counts(compiled.as_text())
    record_property("bytes_per_device", per_device)
    record_property("collectives", counts)
    assert per_device < V5E_HBM_BYTES, per_device
    # the gradient exchange is in the program; which kinds the compiler
    # keeps for torus2d's phases is recorded, not asserted
    assert sum(counts.values()) > 0, counts
