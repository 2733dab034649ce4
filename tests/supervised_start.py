"""Child entry for the supervision test: pin the CPU platform and the
test's local device count (the supervisor's environment is the test
runner's, which may name another platform), then run the real
``kubeml start``. The supervisor launches this exactly like it would launch
``python -m kubeml_tpu.cli start`` in production."""

import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices",
                  int(os.environ.get("KUBEML_TEST_LOCAL_DEVICES", "2")))

from kubeml_tpu.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(["start"]))
