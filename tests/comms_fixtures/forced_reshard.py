"""Seeded implicit-reshard fixture for ``--comms PATH``.

A real traced program with ONE forced mid-program reshard: a shard_map
over a private 2-device ``tp`` mesh whose body ``ppermute``s its shard to
the neighbor chip. GSPMD compiles that to exactly one collective-permute
— a collective no declared layout transition explains (the fixture
declares none), so the strict fixture pass must report exactly one
``implicit-reshard`` HIGH and the CLI must exit 1.

Degrades honestly on a 1-device host (no second chip to permute to, no
collective, no finding) — the tests run it under the 8-device CPU env.
"""


def make_program():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()[:2]
    mesh = Mesh(np.array(devs), ("tp",))
    n = len(devs)

    def body(x):
        # the seeded violation: rotate shards one chip to the right
        return jax.lax.ppermute(x, "tp",
                                [(i, (i + 1) % n) for i in range(n)])

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("tp"),
                           out_specs=P("tp")))
    return fn, (jnp.arange(8, dtype=jnp.float32),)
