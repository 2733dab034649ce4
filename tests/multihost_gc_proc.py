"""Subprocess entry for the broadcast-key GC test (tests/test_multihost.py):
leader broadcasts past a shrunken GC window and proves old keys were deleted
from the coordination-service KV store while recent ones survive."""

import sys


def main() -> int:
    pid = int(sys.argv[1])
    port = sys.argv[2]

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 1)
    jax.distributed.initialize(f"127.0.0.1:{port}", 2, pid)

    from kubeml_tpu.parallel.distributed import get_dist_context

    dist = get_dist_context()
    dist.BCAST_GC_LAG = 8  # shrink the window so GC actually runs
    n = 20
    for i in range(n):
        v = dist.broadcast_obj({"i": i} if dist.is_leader else None)
        assert v["i"] == i
    if not dist.is_leader:
        print("RESULT follower_ok", flush=True)
        dist.put("kubeml/test-exit/1", "1")  # see exit alignment below
        return 0

    def present(key):
        client = dist._client
        if not hasattr(client, "key_value_try_get"):
            # older jaxlib: probe with a short blocking get (ms timeout)
            try:
                client.blocking_key_value_get(key, 200)
                return True
            except Exception:
                return False
        try:
            return client.key_value_try_get(key) is not None
        except Exception as e:  # NOT_FOUND raises on this jaxlib
            if "NOT_FOUND" in str(e):
                return False
            raise

    old_deleted = not present("kubeml/bcast/0")
    recent_present = present(f"kubeml/bcast/{n - 1}")
    print(f"RESULT old_deleted={old_deleted} recent_present={recent_present}",
          flush=True)
    # exit alignment (same as multihost_proc.py): the leader hosts the
    # coordination service and must exit LAST or the follower's agent FATALs
    # with a dirty returncode. Follower PUTs an exit key (no reads), leader
    # collects it before exiting.
    dist.get("kubeml/test-exit/1", timeout_s=30)
    return 0


if __name__ == "__main__":
    rc = main()
    # same teardown-segfault guard as multihost_proc.py: jax.distributed's
    # Gloo client can SIGSEGV in C++ destructors at exit; results are
    # already flushed by now
    sys.stdout.flush()
    sys.stderr.flush()
    import os

    os._exit(rc)
