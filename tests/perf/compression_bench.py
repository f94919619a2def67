"""1-bit compressed allreduce benchmark — reference tests/onebit/
test_nccl_perf.py role, on a forced multi-device CPU mesh (or a real TPU
slice when available).

Run directly: python tests/perf/compression_bench.py [numel]
"""

import functools
import os
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))


def main(numel=8_388_608):
    import numpy as np
    import jax
    if "xla_force_host_platform_device_count" in \
            os.environ.get("XLA_FLAGS", ""):
        # XLA_FLAGS must be set at process start; the platform switch must
        # happen through jax.config BEFORE first device use
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from deepspeed_tpu.parallel import compression as comp
    from jax import shard_map

    n = len(jax.devices())
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    buf = jnp.zeros((n, numel), jnp.float32) + 0.01
    we = jnp.zeros((n, numel), jnp.float32)
    se = jnp.zeros((n, numel // n), jnp.float32)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P("data"),) * 3,
                       out_specs=(P("data"),) * 3)
    def run(b, w, s):
        o, w2, s2 = comp.compressed_allreduce(b[0], w[0], s[0], "data")
        return o[None], w2[None], s2[None]

    o, we, se = run(buf, we, se)
    jax.block_until_ready(o)
    t0 = time.perf_counter()
    for _ in range(10):
        o, we, se = run(buf, we, se)
    jax.block_until_ready(o)
    dt = (time.perf_counter() - t0) / 10
    print(f"1-bit allreduce {numel/1e6:.0f}M floats on {n} devices: "
          f"{dt*1e3:.1f} ms ({numel*4/dt/1e9:.2f} GB/s equivalent dense)")


if __name__ == "__main__":
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # re-exec with the multi-device CPU env (XLA_FLAGS is read at
        # interpreter start)
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8")
        os.execve(sys.executable, [sys.executable, __file__] + sys.argv[1:],
                  env)
    main(*(int(a) for a in sys.argv[1:]))
