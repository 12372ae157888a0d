"""Quickstart: share one accelerator between 8 small training jobs with
triples mode — the paper's core workflow in ~40 lines of user code.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro import optim
from repro.core import Triples, NodeSpec, packing, plan
from repro.core.monitor import profile_fn
from repro.data.mnist import synthetic_mnist
from repro.models import lenet


def main():
    # 1. The paper's triplet: 1 node, 8 processes, sharing its accelerators.
    node = NodeSpec(chips_per_node=1, hbm_per_chip=16e9)
    trip = Triples(nnode=1, nppn=8, ntpp=1)
    p = plan(n_tasks=8, triples=trip, node_spec=node)
    print(f"pack factor: {p.pack_factor} tasks/chip "
          f"(sharing={trip.is_sharing(node)})")

    # 2. Define the per-task step (LeNet-4/MNIST, as in the paper §III-A).
    opt = optim.sgd()

    def step(params, opt_state, batch, lr):
        loss, grads = jax.value_and_grad(lenet.loss)(params, batch)
        upd, opt_state = opt.update(grads, opt_state, params, lr)
        return optim.apply_updates(params, upd), opt_state, loss

    # 3. LLload-style pre-flight: does 8-way packing fit the HBM budget?
    prof = profile_fn(step, lenet.init(jax.random.PRNGKey(0)),
                      opt.init(lenet.init(jax.random.PRNGKey(0))),
                      {"image": jnp.zeros((64, 28, 28, 1)),
                       "label": jnp.zeros((64,), jnp.int32)},
                      jnp.float32(0.05))
    print(f"per-task memory: {prof.resident_bytes/1e6:.1f} MB "
          f"-> 8 packed ≈ {8*prof.resident_bytes/1e6:.0f} MB "
          f"(fits 16GB: {8*prof.resident_bytes < 16e9})")

    # 4. Pack the 8 jobs as vmapped lanes of ONE program and train.
    jobs = packing.PackedJobs.create(
        lenet.init, opt.init, step, jax.random.PRNGKey(0), n_lanes=8,
        hparams=jnp.asarray([0.01 * (i + 1) for i in range(8)], jnp.float32))
    for s in range(10):
        batch = packing.stack_trees([
            {k: jnp.asarray(v) for k, v in
             synthetic_mnist(64, s, seed=i).items()} for i in range(8)])
        metrics = jobs.run_step(batch)
    print("final per-task losses:",
          [f"{float(l):.3f}" for l in metrics])


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
