"""TPL012 positive: a psum whose measured wire bytes exceed the
committed budget. tests/test_ircheck.py traces ``build``'s program,
summarizes its collectives (``parallel.comms.collective_summary``) and
diffs them against ``BUDGET`` via ``analysis.ircheck.budget_findings``
— the finding anchors at the BUDGET line (the committed number under
review), pinned by the EXPECT marker above it."""


def build(jax, jnp):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from lightgbm_tpu.parallel.mesh import DATA_AXIS, make_mesh
    mesh = make_mesh(8, devices=jax.devices("cpu"))
    fn = shard_map(lambda x: jax.lax.psum(x, DATA_AXIS), mesh=mesh,
                   in_specs=P(DATA_AXIS), out_specs=P(),
                   check_vma=False)
    return fn, (jnp.ones((8, 32), jnp.float32),)


# the per-shard psum operand is (1, 32) f32 = 128 wire bytes; this
# budget admits only 16, so the measured payload exceeds it
# EXPECT: TPL012
BUDGET = {"wire_bytes": 16, "justification": "deliberately too small"}
