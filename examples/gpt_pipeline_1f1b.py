"""GPT trained under the 1F1B pipeline schedule, end to end.

Beyond the reference's scope (it has no pipeline parallelism): the
Block stack is split into one stage per device; the embedding is stage
0's entry edge and the head+loss stage P-1's exit edge, and after a
P-tick warmup each device runs one forward and one backward microbatch
per tick (`parallel.pipeline.pipeline_train_step_1f1b`). In-flight
activation storage is a 2P-slot ring buffer per device — independent of
the microbatch count — which is what lets long gradient-accumulation
horizons fit. Run on the virtual CPU mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/gpt_pipeline_1f1b.py
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
import optax

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from kungfu_tpu.models import GPTConfig, GPTLM, stack_gpt_blocks
from kungfu_tpu.models.gpt import gpt_pipeline_train_step


def main():
    n = jax.device_count()
    stages = 4 if n >= 4 else n
    microbatches = 8
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=stages,
                    num_heads=8, intermediate_size=256, max_position=128,
                    dtype=jnp.float32)
    model = GPTLM(cfg)
    print(f"{stages} pipeline stages x {cfg.num_layers // stages} "
          f"layer(s), {microbatches} microbatches "
          f"({jax.devices()[0].platform})")

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 64)))
    params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]
    outer, stacked = stack_gpt_blocks(params, stages)

    mesh = Mesh(np.array(jax.devices()[:stages]), ("pipe",))
    mapped = shard_map(
        lambda o, s, t: gpt_pipeline_train_step(
            cfg, o, s, t, "pipe", num_microbatches=microbatches),
        mesh=mesh, in_specs=(P(), P("pipe"), P()),
        out_specs=(P(), P(), P("pipe")), check_vma=False)

    tx = optax.adam(1e-2)
    so, ss = tx.init(outer), tx.init(stacked)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def step(outer, stacked, so, ss, t):
        loss, g_o, g_s = mapped(outer, stacked, t)
        uo, so2 = tx.update(g_o, so, outer)
        us, ss2 = tx.update(g_s, ss, stacked)
        return (optax.apply_updates(outer, uo),
                optax.apply_updates(stacked, us), so2, ss2, loss)

    for i in range(30):
        outer, stacked, so, ss, loss = step(outer, stacked, so, ss,
                                            tokens)
        if i % 5 == 0 or i == 29:
            print(f"step {i:3d}  loss {float(loss):.4f}")
    uniform = float(np.log(cfg.vocab_size))
    print(f"uniform baseline {uniform:.4f}; the same loss trajectory as "
          "the single-device model (tests/test_gpt.py proves gradient "
          "equality to tolerance)")


if __name__ == "__main__":
    main()
