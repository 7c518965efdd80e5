"""The served path's Pallas kernels compile for a TPU v5e, without a chip.

Each test lowers one kernel for a described (not attached) ``v5e:2x2``
topology and compiles it with the TPU compiler installed next to jaxlib:
what Mosaic refuses here — misaligned blocks, unsupported reductions, a
VMEM working set past the scoped limit — would be refused on the chip.
Shapes are the real widths: the bucket-probe groups the σ pass sends to
the kernel on a TPU (every group: probe rows from 8 wide up to hub tiles
of 2048·2^k, at the chunk sizes ``edge_sims`` picks), 2- and 8-word
sketches, and 128/256-sample SimHash blocks.

The topology is described inside a module fixture (never at import, in a
``skipif`` or in ``parametrize``): only one process may load the TPU
library, and each test worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.similarity import CHUNK_ELEMS, KERNEL_LANE_EDGES
from repro.kernels.bucket_probe import bucket_probe
from repro.kernels.hamming import hamming_distance
from repro.kernels.simhash import simhash_pack


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but not read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("p,t", [(256, 256), (2048, 2048), (2048, 16384)])
def test_bucket_probe_compiles_for_v5e(one_chip, p, t):
    e = 256
    ids = lambda w: _spec(one_chip, (e, w), jnp.int32)      # noqa: E731
    wts = lambda w: _spec(one_chip, (e, w), jnp.float32)    # noqa: E731
    compiled = bucket_probe.lower(
        ids(p), wts(p), ids(t), wts(t), be=256, bt=256,
        interpret=False).compile()
    _assert_kernel(compiled)


def _narrow_chunks():
    """(probe width, target width, chunk edges) of the narrow σ groups:
    probe rows 8–128 wide against targets of their own width up to a
    16-tile hub, each at ``edge_sims``'s largest chunk for that pair and at
    its smallest (a group of at most 64 edges)."""
    out = []
    for p in (8, 16, 32, 64, 128):
        for t in (8, 256, 2048, 32768):
            if p > t:       # the probe side is the narrower one
                continue
            cap = CHUNK_ELEMS // (p + t)
            cap = max(1 << (cap.bit_length() - 1), KERNEL_LANE_EDGES)
            out += [(p, t, min(cap, 1 << 16)), (p, t, 64)]
    return out


@pytest.mark.parametrize("p,t,e", _narrow_chunks())
def test_bucket_probe_compiles_for_narrow_groups(one_chip, p, t, e):
    bt = min(256, t)                  # ``probe_operands``' target tile
    ids = lambda w: _spec(one_chip, (e, w), jnp.int32)      # noqa: E731
    wts = lambda w: _spec(one_chip, (e, w), jnp.float32)    # noqa: E731
    compiled = bucket_probe.lower(
        ids(p), wts(p), ids(t), wts(t), be=min(256, e), bt=bt,
        interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("words", [2, 8])
def test_hamming_compiles_for_v5e(one_chip, words):
    sk = _spec(one_chip, (4096, words), jnp.uint32)
    compiled = hamming_distance.lower(sk, sk, be=1024,
                                      interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("k", [128, 256])
def test_simhash_pack_compiles_for_v5e(one_chip, k):
    n = 256
    compiled = simhash_pack.lower(
        _spec(one_chip, (n, n), jnp.float32),
        _spec(one_chip, (n, k), jnp.float32), interpret=False).compile()
    _assert_kernel(compiled)
