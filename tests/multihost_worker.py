"""Worker process for the 2-process multi-host bring-up test.

Run by tests/test_multihost.py, one subprocess per "host": each process owns
4 virtual CPU devices (xla_force_host_platform_device_count=4) and joins a
2-process jax.distributed cluster through the SAME production path a real
multi-host TPU deployment uses — `init_distributed` → `build_mesh` →
sharded train step (docs/DEPLOYMENT.md Topology 3). Nothing here is
test-double'd: the coordinator service, cross-process device discovery, and
the XLA collectives the train step's gradient psum lowers to are all real.

Two scenarios, selected by SYMBIONT_MULTIHOST_MODE:
- "dp" (default): pure data-parallel mesh over all 8 devices; the gradient
  psum over 'data' crosses the process boundary.
- "tp": a [4, 2] mesh whose 'tensor' axis PAIRS one device from each
  process, so every tensor-parallel collective in the train step (activation
  psums, gradient reductions) physically crosses hosts — the megatron-style
  sharding proven over DCN, not just ICI.

Protocol (parsed by the parent test): prints one line
    MULTIHOST ok global=<N> local=<n> procs=<P> loss=<float> sum=<int>
and exits 0; any assertion failure exits nonzero with a traceback.
"""

import os
import sys


def main() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from symbiont_tpu.models import gpt as gpt_mod
    from symbiont_tpu.parallel.mesh import build_mesh, init_distributed
    from symbiont_tpu.train.trainer import TrainState, _adamw, lm_train_step

    # coordinator/process topology arrives via SYMBIONT_COORDINATOR /
    # SYMBIONT_NUM_PROCESSES / SYMBIONT_PROCESS_ID (set by the parent test),
    # exactly as a launcher would set them on a non-TPU cluster.
    n_global = init_distributed()
    n_local = len(jax.local_devices())
    procs = jax.process_count()
    assert procs == 2, f"expected 2 processes, got {procs}"
    assert n_global == 2 * n_local, (n_global, n_local)

    mode = os.environ.get("SYMBIONT_MULTIHOST_MODE", "dp")
    if mode == "tp":
        # tensor axis spans the processes: pair device i of process 0 with
        # device i of process 1, so TP collectives ride the cross-host link
        devs = np.asarray(jax.devices()).reshape(procs, n_local).T
        mesh = jax.sharding.Mesh(devs, ("data", "tensor"))
        assert all({d.process_index for d in row} == {0, 1}
                   for row in devs), "each tensor pair must span processes"
    else:
        # one DP mesh over the WHOLE cluster: both processes' devices
        mesh = build_mesh([n_global, 1])
    assert {d.process_index for d in mesh.devices.flat} == {0, 1}, \
        "mesh must span both processes"

    cfg = gpt_mod.GPTConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        intermediate_size=128, max_position_embeddings=32,
        arch="llama", num_kv_heads=2, dtype="float32",
        tie_word_embeddings=True)

    if mode == "tp":
        _run_tp(mesh, cfg, n_global, n_local, procs)
        return

    tx = _adamw(1e-3)
    rep = NamedSharding(mesh, P())

    # init params + opt state INSIDE jit with replicated out_shardings: under
    # multi-process JAX, eager ops on non-addressable arrays are invalid, so
    # all global state is born on-device from a shared seed.
    @jax.jit
    def init_state(key):
        params = gpt_mod.init_params(key, cfg)
        return TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))

    state = jax.jit(init_state, out_shardings=rep)(jax.random.key(0))

    # global batch sharded over 'data': each process materializes only ITS
    # addressable shards; rows therefore physically live on different hosts.
    # _make_batch also proves a collective crosses the process boundary (a
    # global sum of the sharded array must equal the host-known total).
    batch, total = _make_batch(mesh, cfg, B=n_global)

    # ONE cross-process DP train step (gradient psum over 'data' spans hosts)
    state, metrics = lm_train_step(state, batch, cfg, tx)
    loss = float(metrics["loss"].addressable_shards[0].data)
    assert np.isfinite(loss), loss
    assert int(state.step.addressable_shards[0].data) == 1

    print(f"MULTIHOST ok global={n_global} local={n_local} procs={procs} "
          f"loss={loss:.6f} sum={total}", flush=True)


def _make_batch(mesh, cfg, B: int, S: int = 16):
    """Shared batch protocol for both scenarios: same seed → same global
    view on every process; rows sharded over 'data' so each process
    materializes only its addressable shards. Returns (batch, global_sum)
    where global_sum proves a collective crossed the process boundary."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    rng = np.random.default_rng(7)
    full_ids = rng.integers(1, cfg.vocab_size, (B, S)).astype(np.int32)
    bs = NamedSharding(mesh, P("data"))
    ids = jax.make_array_from_callback((B, S), bs, lambda idx: full_ids[idx])
    mask = jax.make_array_from_callback(
        (B, S), bs, lambda idx: np.ones((B, S), np.int32)[idx])
    total = int(jax.jit(jnp.sum)(ids).addressable_shards[0].data)
    assert total == int(full_ids.sum()), (total, int(full_ids.sum()))
    return {"ids": ids, "mask": mask}, total


def _run_tp(mesh, cfg, n_global: int, n_local: int, procs: int) -> None:
    """Cross-host tensor parallelism through the PRODUCTION train step:
    a TrainState born TP-sharded (params megatron-split over the 'tensor'
    axis that pairs devices ACROSS the two processes, AdamW mu/nu mirroring
    the param shardings), driven through trainer.lm_train_step — so the
    exact code a real deployment runs does its forward, backward, and
    optimizer update across the host boundary."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from symbiont_tpu.models import gpt as gpt_mod
    from symbiont_tpu.parallel.sharding import gpt_param_sharding
    from symbiont_tpu.train.trainer import TrainState, _adamw, lm_train_step

    tx = _adamw(1e-3)
    rep = NamedSharding(mesh, P())
    template = jax.eval_shape(lambda k: gpt_mod.init_params(k, cfg),
                              jax.random.key(0))
    spec = gpt_param_sharding(mesh, template, arch="llama")
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                            is_leaf=lambda x: isinstance(x, P))

    # optimizer-state shardings mirror the params (adam mu/nu share the
    # param tree structure; counts and other scalars replicate)
    def opt_sharding(os_shape):
        if isinstance(os_shape, optax.ScaleByAdamState):
            return optax.ScaleByAdamState(count=rep, mu=param_sh, nu=param_sh)
        return jax.tree.map(lambda _: rep, os_shape)

    opt_shape = jax.eval_shape(tx.init, template)
    state_sh = TrainState(param_sh,
                          tuple(opt_sharding(s) for s in opt_shape), rep)

    def init_state(key):
        params = gpt_mod.init_params(key, cfg)
        return TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))

    state = jax.jit(init_state, out_shardings=state_sh)(jax.random.key(0))
    # q kernels really live split over the cross-host tensor axis
    assert "tensor" in str(
        state.params["layers"][0]["q"]["kernel"].sharding.spec)

    batch, total = _make_batch(mesh, cfg, B=mesh.shape["data"])

    # ONE production train step: every TP collective and the sharded AdamW
    # update cross the process boundary
    state, metrics = lm_train_step(state, batch, cfg, tx)
    loss = float(metrics["loss"].addressable_shards[0].data)
    assert np.isfinite(loss), loss
    gnorm = float(metrics["grad_norm"].addressable_shards[0].data)
    assert np.isfinite(gnorm) and gnorm > 0, gnorm
    assert int(state.step.addressable_shards[0].data) == 1
    # updated params kept the TP sharding through the optimizer update
    assert "tensor" in str(
        state.params["layers"][0]["q"]["kernel"].sharding.spec)

    print(f"MULTIHOST ok global={n_global} local={n_local} procs={procs} "
          f"loss={loss:.6f} sum={total}", flush=True)


if __name__ == "__main__":
    try:
        main()
    except Exception:
        import traceback

        traceback.print_exc()
        sys.exit(1)
