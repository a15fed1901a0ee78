"""Child process for multi-process cluster harnesses
(tests/test_distnode.py): brings up a full DistClusterNode under the given
name, joins the seed, serves until killed."""

import sys
import time

import jax

# these harnesses run the product on CPU (same pattern as
# tests/conftest.py): the parent may hold the chip, the child never asks
jax.config.update("jax_platforms", "cpu")

from opensearch_tpu.cluster.distnode import DistClusterNode  # noqa: E402


def main():
    seed = sys.argv[1]
    name = sys.argv[2] if len(sys.argv) > 2 else "b"
    n = DistClusterNode(name, seed=seed)
    print(f"READY {n.addr}", flush=True)
    while True:
        time.sleep(1)


if __name__ == "__main__":
    main()
