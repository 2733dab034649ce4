"""Subprocess entry for tests/test_kernel_lowering.py: compile every kernel
case with the REAL Mosaic compiler, ahead of time, against a chipless TPU
topology description (libtpu builds one without hardware). Runs in its own
process because it loads the TPU plugin, which the test session must not.

Prints ``OK <case>`` / ``FAIL <case>: <error>`` per case; exit 0 when all
compiled, 1 when any failed, 77 when this installation cannot describe a TPU
topology (the caller skips)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # the session's devices stay CPU
# libtpu asks the environment what host it is on; there is none
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]  # kernel_shapes, kubeml_tpu


def main() -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # no libtpu, or one that needs a chip to describe it
        print(f"no TPU topology description here: {type(e).__name__}: {e}")
        return 77
    on_chip = SingleDeviceSharding(topo.devices[0])

    from kernel_shapes import kernel_cases

    failed = 0
    for name, (fn, args) in kernel_cases().items():
        specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip)
                 for a in args]
        try:
            jax.jit(fn).trace(*specs).lower(
                lowering_platforms=("tpu",)).compile()
            print(f"OK {name}", flush=True)
        except Exception as e:  # the compiler's refusal IS the result
            failed += 1
            print(f"FAIL {name}: {type(e).__name__}: {str(e)[:600]}",
                  flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
