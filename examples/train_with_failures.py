"""End-to-end driver: train a ~100M-param LM for a few hundred steps with
LowDiff per-iteration checkpointing and two injected failures.

This is the deliverable-(b) end-to-end example; it delegates to the real
launcher (repro.launch.train). Expect ~10-20 min on one CPU core; pass
--quick for a 40-step smoke variant.

Run:  PYTHONPATH=src python examples/train_with_failures.py [--quick]
"""
import argparse

from repro.configs import get_config


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    q = ap.parse_args()

    argv = argparse.Namespace(
        arch="gpt2-l", reduced=False, steps=40 if q.quick else 300,
        batch=2, seq=64 if q.quick else 128, lr=1e-3, rho=0.01,
        strategy="lowdiff", full_interval=20, batch_size=2,
        ckpt_dir="/tmp/repro_e2e", clean=True,
        fail_at=20 if q.quick else 150, seed=0, log_every=10)
    # ~100M model: trim gpt2-l (762M) to a 12-layer/768-d variant
    cfg = get_config("gpt2-l").replace(
        n_layers=12, d_model=768, n_heads=12, n_kv_heads=12, d_ff=3072,
        vocab=16384 if q.quick else 50257)
    if q.quick:
        cfg = cfg.reduced()

    import repro.launch.train as T
    orig = T.get_config
    T.get_config = lambda name: cfg
    try:
        losses = T.run(argv).losses
    finally:
        T.get_config = orig
    assert losses[-1] < losses[0], "loss should decrease"
    print("\nend-to-end driver finished; loss decreased "
          f"{losses[0]:.3f} -> {losses[-1]:.3f} across an injected failure.")


if __name__ == "__main__":
    main()
