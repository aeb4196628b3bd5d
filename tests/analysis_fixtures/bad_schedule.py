"""Seeded schedule violation: a chunk step that reads the pre-commit
table AFTER the boundary commit and issues a second scatter-add.
``python -m repro.analysis --pass schedule <this file>`` must exit
non-zero with findings at the lines below."""


def _bad_step(table, pages, w):
    import jax.numpy as jnp

    committed = table.at[pages, 2].add(w, mode="drop")
    stale = table[pages, 3]  # stale read of the pre-commit table
    committed = committed.at[pages, 4].add(stale)  # second scatter-add
    return jnp.sum(committed)


def reprolint_case():
    def make():
        import jax.numpy as jnp

        i32 = jnp.int32
        args = (jnp.zeros((16, 8), i32), jnp.arange(4, dtype=i32),
                jnp.ones(4, i32))
        return _bad_step, args

    return {"kind": "schedule", "make": make}
