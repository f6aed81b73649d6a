"""Both Pallas kernels compile for a TPU v5e chip at the sizes the engine
feeds them.  The chip is described, not attached: the TPU compiler
refuses here what the chip would refuse (tile alignment, VMEM/SMEM use),
at no chip time.  Nothing runs, so these say nothing about results or
speed — chip_smoke.py checks those on the chip."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import chunker, fphash


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


def _compiled_kernel(compiled):
    print(compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()


def test_chunker_compiles_at_8mib(one_chip):
    n = 8 << 20
    nrows = -(-n // chunker.ROW_STRIDE)
    nrows = -(-nrows // chunker.SUBLANES) * chunker.SUBLANES
    rows = jax.ShapeDtypeStruct((nrows, chunker.ROW_LEN), jnp.uint8,
                                sharding=one_chip)
    _compiled_kernel(chunker._run.lower(
        rows, window=48, q=12, seed=0xF0B, interpret=False).compile())


@pytest.mark.parametrize("nchunks,nblocks", [(2048, 1), (1024, 8)])
def test_fphash_many_compiles(one_chip, nchunks, nblocks):
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    _compiled_kernel(fphash._run.lower(
        arg((nchunks,), jnp.int32),
        arg((nchunks, nblocks * 8, 128), jnp.uint32),
        arg((8, 128), jnp.uint32), interpret=False).compile())
