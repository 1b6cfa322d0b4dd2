"""Quickstart: per-iteration differential checkpointing with LowDiff.

Trains a small GPT-2-family model on CPU with checkpointing *every
iteration*, then simulates a crash and recovers — demonstrating that the
recovered state equals the live state (the compressed gradient IS the
differential checkpoint, Finding 1 of the paper).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import shutil

import jax
import numpy as np

from repro.checkpoint import StoreConfig, TierSpec
from repro.configs import get_config
from repro.core.engine import EngineConfig, make_engine
from repro.core.steps import init_state
from repro.data.synthetic import TokenStream
from repro.models.registry import build_model
from repro.obs.trace import TRACER

CKPT_DIR = "/tmp/repro_quickstart"


def main():
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    cfg = get_config("gpt2-l").reduced()
    model = build_model(cfg)
    print(f"model: {cfg.name} ({model.n_params() / 1e6:.1f}M params)")

    # the store is a declarative tier stack: swap TierSpec("local") for
    # TierSpec("sharded")/TierSpec("memory")/... — or prepend
    # TierSpec("peer", replicas=2) for Checkmate-style peer replication
    store = StoreConfig(CKPT_DIR, tiers=[TierSpec("local")],
                        retention_fulls=2).build()
    lowdiff = make_engine(
        EngineConfig(strategy="lowdiff", rho=0.01, lr=1e-3,
                     full_interval=10, batch_size=2),
        model, store=store)
    state = init_state(model, jax.random.PRNGKey(0))
    stream = TokenStream(cfg, seq_len=64, batch=4)

    print("\ntraining 25 steps, checkpointing EVERY iteration...")
    TRACER.enable()     # spans: where the training thread spent its time
    for t in range(25):
        state, metrics = lowdiff.train_step(state, next(stream))
        if (t + 1) % 5 == 0:
            print(f"  step {t + 1:3d}  loss {float(metrics['loss']):.4f}")
    lowdiff.flush()

    s = lowdiff.stats()
    print(f"\ncheckpoints: {s['store']['fulls']} full, "
          f"{s['store']['batches']} batched-diff writes "
          f"({s['store']['bytes'] / 2 ** 20:.1f} MiB total)")
    in_loop = sum(e[5] - e[4] for e in TRACER.events()
                  if e[0] in ("engine.queue_put", "snapshot.issue"))
    print(f"checkpointing time inside the training loop: "
          f"{in_loop * 1e3:.1f} ms over 25 steps")
    TRACER.disable()

    print("\n*** simulating failure; recovering from storage ***")
    recovered, n = lowdiff.recover()
    err = max(float(np.max(np.abs(np.asarray(a, np.float32)
                                  - np.asarray(b, np.float32))))
              for a, b in zip(jax.tree.leaves(recovered["params"]),
                              jax.tree.leaves(state["params"])))
    print(f"recovered to step {int(recovered['step'])} "
          f"(replayed {n} differentials); max |Δparam| vs live = {err:.2e}")
    assert err < 1e-6
    lowdiff.close()
    print("OK — recovery is exact.")


if __name__ == "__main__":
    main()
