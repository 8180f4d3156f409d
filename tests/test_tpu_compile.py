"""Compile the served path's Pallas kernels for a described TPU v5e.

The interpreter cannot see what the TPU compiler refuses: tiles that are
not aligned to the (8, 128) layout, and more VMEM than a kernel may use.
Each case lowers and compiles one kernel with ``interpret=False`` for one
chip of a described ``v5e:2x2`` topology (no chip attached) and looks for
the Mosaic kernel (``tpu_custom_call``) in the compiled program.  Widths:
the KDD smoke's (d=34, m=3 and m=20 centers) and the W1 vector-index
coarse quantizer's (d=128, m=4096 centers), for every metric each kernel
implements (the lloyd kernel has no l1 path).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every pytest worker imports
this file.  The persistent compilation cache is off around the cases (a
compile for a described chip cannot be read back without one).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.lloyd.kernel import lloyd_step_pallas
from repro.kernels.pdist.kernel import min_argmin_pallas
from repro.kernels.score.kernel import score_pallas

SHAPES = {"kdd-m3": (16384, 3, 34), "kdd-m20": (16384, 20, 34),
          "w1": (16384, 4096, 128)}   # (n rows, m centers, d)
CASES = [(op, metric, shape)
         for op, metrics in (("min_argmin", ("l2sq", "l2", "l1")),
                             ("score", ("l2sq", "l2", "l1")),
                             ("lloyd_step", ("l2sq", "l2")))
         for metric in metrics for shape in SHAPES]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


@pytest.mark.parametrize("op,metric,shape", CASES)
def test_kernel_compiles_for_v5e(one_chip, op, metric, shape):
    n, m, d = SHAPES[shape]

    def spec(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    if op == "min_argmin":
        fn = jax.jit(lambda x, c: min_argmin_pallas(x, c, metric=metric))
        args = (spec(n, d), spec(m, d))
    elif op == "score":
        fn = jax.jit(lambda x, c, thr: score_pallas(x, c, thr, metric=metric))
        args = (spec(n, d), spec(m, d), spec())
    else:
        fn = jax.jit(lambda x, w, c: lloyd_step_pallas(x, w, c,
                                                       metric=metric))
        args = (spec(n, d), spec(n), spec(m, d))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
