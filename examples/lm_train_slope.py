"""End-to-end LM training with SLOPE-path regularization + fault tolerance.

    PYTHONPATH=src python examples/lm_train_slope.py

Trains a reduced smollm-family model for a few hundred steps with the
sorted-ℓ1 prox applied to the embedding along a σ-path, the strong rule
screening the active rows each log step.  Mid-run the script simulates a
preemption (SIGTERM to itself), then restarts from the checkpoint and
finishes — demonstrating the trainer's checkpoint/restart path.
"""

import dataclasses
import os
import signal

import numpy as np

from repro.compile_cache import use_checkout_cache

use_checkout_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.configs import get_config
from repro.models.slope_reg import SlopeRegConfig
from repro.optim import AdamWHyper
from repro.train import TrainConfig, Trainer, latest_step

CKPT = "runs/example_slope_lm"


def main():
    import shutil

    shutil.rmtree(CKPT, ignore_errors=True)  # fresh demo run
    cfg = dataclasses.replace(get_config("smollm-360m").reduced(), n_layers=4)
    slope = SlopeRegConfig(targets=("embed",), q=0.1, sigma0=0.3,
                           sigma_ratio=5e-2, total_steps=300, screen_every=50)
    tc = TrainConfig(steps=300, ckpt_every=50, log_every=25, ckpt_dir=CKPT,
                     slope=slope)

    # phase 1: train until a simulated preemption at step ~120
    trainer = Trainer(cfg, tc, hyper=AdamWHyper(lr=2e-3), global_batch=8,
                      seq_len=64)
    orig = trainer.train_step
    calls = {"n": 0}

    def preempting(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 120:
            print(">>> simulating preemption (SIGTERM)")
            os.kill(os.getpid(), signal.SIGTERM)
        return orig(*a, **kw)

    trainer.train_step = preempting
    out1 = trainer.run()
    print(f"phase 1 ended at step {out1['final_step']} "
          f"(preempted={out1['preempted']}); checkpoint at step "
          f"{latest_step(CKPT)}")

    # phase 2: fresh trainer resumes from the checkpoint and finishes
    out2 = Trainer(cfg, tc, hyper=AdamWHyper(lr=2e-3), global_batch=8,
                   seq_len=64).run()
    embed = np.asarray(out2["params"]["embed"])
    print(f"phase 2 finished at step {out2['final_step']}")
    print(f"final loss: {out2['metrics'][-1]['loss']:.4f}")
    total = embed.size
    print("\nSLOPE σ-path trajectory (strong → weak regularization, paper §3.1.2):")
    print("  step   nnz(embed)   strong-rule k̂")
    for m in out1["metrics"] + out2["metrics"]:
        if "slope/embed/nnz" in m:
            print(f"  {m['step']:4d}   {m['slope/embed/nnz']:7d}/{total}"
                  f"   {m['slope/embed/strong_k']:8d}")
    print("(early path: strong σ ⇒ the prox zeroes coefficients and the strong rule "
          "screens them; σ decays along the path so coefficients re-enter — "
          "the paper's path semantics inside the training loop)")


if __name__ == "__main__":
    main()
