"""TPL012 negative: the same psum as tpl012_pos with a budget that
matches the measured payload exactly — measured <= committed on every
metric, so no finding."""


def build(jax, jnp):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from lightgbm_tpu.parallel.mesh import DATA_AXIS, make_mesh
    mesh = make_mesh(8, devices=jax.devices("cpu"))
    fn = shard_map(lambda x: jax.lax.psum(x, DATA_AXIS), mesh=mesh,
                   in_specs=P(DATA_AXIS), out_specs=P(),
                   check_vma=False)
    return fn, (jnp.ones((8, 32), jnp.float32),)


BUDGET = {"n_collectives": 1, "wire_bytes": 128,
          "post_reduction_bytes": 128,
          "justification": "one (1, 32) f32 psum operand each way"}
