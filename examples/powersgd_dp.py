"""PowerSGD-TSQR data-parallel training with REAL collectives.

Trains a two-layer MLP regression model under ``shard_map`` on a
(data=2 × model=4) device mesh (8 forced host devices), exchanging
gradients as rank-r factors: the left factor is orthonormalized with the
paper's fault-tolerant butterfly TSQR over the model axis, and a
mid-training simulated rank failure is absorbed by the Self-Healing
variant without interrupting the run.

Reports data-axis bytes: compressed vs dense all-reduce.

  JAX_PLATFORMS=cpu python examples/powersgd_dp.py   # 8 host devices
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.env import force_host_devices      # noqa: E402

force_host_devices(8)            # on the CPU; precedes the first jax import

import jax                                           # noqa: E402
import jax.numpy as jnp                              # noqa: E402
from jax import lax                                  # noqa: E402
from jax.sharding import PartitionSpec as P           # noqa: E402

from repro.collective import FaultSpec, ShardMapComm  # noqa: E402
from repro.compat import make_mesh, shard_map        # noqa: E402
from repro.optim import powersgd                     # noqa: E402

D, M = 2, 4                    # data x model mesh
DIN, DH, DOUT = 64, 128, 32    # w1 rows sharded over model
RANK = 8
STEPS = 80
LR = 0.3


def main():
    mesh = make_mesh((D, M), ("data", "model"))
    key = jax.random.key(0)
    w_true1 = jax.random.normal(key, (DIN, DH)) / 8
    w_true2 = jax.random.normal(jax.random.fold_in(key, 1), (DH, DOUT)) / 8

    # data: each data-replica sees its own stream
    x = jax.random.normal(jax.random.fold_in(key, 2), (D, 256, DIN))
    y = jnp.maximum(x @ w_true1, 0) @ w_true2

    # small random init (zero init would make the rank-r sketch singular:
    # QR of an all-zero P̄ has no meaning)
    w1 = jax.random.normal(jax.random.fold_in(key, 8), (DIN, DH)) * 0.05
    w2 = jax.random.normal(jax.random.fold_in(key, 9), (DH, DOUT)) * 0.05
    psgd_cfg = powersgd.PowerSGDConfig(rank=RANK, error_feedback=True,
                                       variant="selfhealing")
    q1 = jax.random.normal(jax.random.fold_in(key, 3), (DH, RANK), jnp.float32)
    e1 = jnp.zeros((DIN, DH), jnp.float32)   # sharded over model rows
    comm = ShardMapComm(M, "model")

    def loss_fn(w1_blk, w2_full, xb, yb):
        # w1 rows sharded over model: gather for the forward (toy scale)
        w1_full = lax.all_gather(w1_blk, "model", axis=0, tiled=True)
        pred = jnp.maximum(xb @ w1_full, 0) @ w2_full
        return jnp.mean((pred - yb) ** 2)

    def make_step(fault_spec):
        def step(w1_blk, w2_full, q, e, xb, yb):
            g1_blk, g2 = jax.grad(loss_fn, argnums=(0, 1))(
                w1_blk, w2_full, xb[0], yb[0])
            # dense path for w2 (small); PowerSGD-TSQR path for w1
            g2_mean = lax.pmean(g2, "data")
            state = {"q": q, "e": e}
            g1_hat, new_state, stats = powersgd.compress_grad(
                g1_blk, state, comm, cfg=psgd_cfg,
                psum_data=lambda v: lax.psum(v, "data"),
                psum_model=lambda v: lax.psum(v, "model"),
                n_data=D, fault_spec=fault_spec)
            return (w1_blk - LR * g1_hat, w2_full - LR * g2_mean,
                    new_state["q"], new_state["e"],
                    jnp.asarray(stats["data_bytes_compressed"]),
                    jnp.asarray(stats["data_bytes_dense"]))

        return jax.jit(shard_map(
            step, mesh=mesh,
            in_specs=(P("model", None), P(), P(), P("model", None),
                      P("data", None, None), P("data", None, None)),
            out_specs=(P("model", None), P(), P(), P("model", None),
                       P(), P()),
        ))

    step_ok = make_step(None)
    step_fault = make_step(FaultSpec.of({1: 1}))   # model-rank 1 dies, respawned

    losses = []
    for i in range(STEPS):
        fn = step_fault if i == STEPS // 2 else step_ok
        w1, w2, q1, e1, b_comp, b_dense = fn(w1, w2, q1, e1, x, y)
        l = float(jnp.mean((jnp.maximum(x[0] @ w1, 0) @ w2 - y[0]) ** 2))
        losses.append(l)
        if i % 10 == 0 or i == STEPS // 2:
            tag = "  <-- rank failure absorbed by self-healing TSQR" \
                if i == STEPS // 2 else ""
            print(f"step {i:3d} loss {l:.5f}{tag}")
    print(f"\nfinal loss {losses[-1]:.5f} (from {losses[0]:.5f})")
    print(f"data-axis bytes/step: compressed={int(b_comp)} "
          f"dense={int(b_dense)} ({float(b_dense)/float(b_comp):.1f}x saved)")
    assert losses[-1] < 0.25 * losses[0]


if __name__ == "__main__":
    main()
