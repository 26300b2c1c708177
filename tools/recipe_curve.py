"""Golden loss-curve harness for the flagship recipes (VERDICT r3 #7).

Ties the ResNet recipe (warmup -> poly, LARS, big-batch-equivalent via
gradient accumulation — models/resnet/README.md:131-149 scaled down)
and the PTB-LM recipe to REPRODUCIBLE curves:

    python tools/recipe_curve.py --record          # write fixtures
    python tools/recipe_curve.py --check           # compare vs fixtures
    python tools/recipe_curve.py --check --tol 0.2 # chip tolerance

``--record`` runs each leg with fixed seeds and stores the per-iteration
loss series (ResNet) / final perplexity (PTB) under tools/fixtures/.
``--check`` re-runs identically and compares windowed-mean loss
trajectories — run on the chip, this replays with the fused Pallas
kernels, so a fused-path numerics regression shows up as curve
divergence rather than surviving unseen (the published 0.76114 top-1
recipe is too big for CI; trajectory-equivalence on the scaled recipe
is the provable invariant).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from types import SimpleNamespace

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


class _LossRecorder:
    """Duck-typed TrainSummary capturing the engine's Loss scalars."""

    def __init__(self):
        self.losses = []

    def add_scalar(self, tag, value, step):
        if tag == "Loss":
            self.losses.append(float(value))
        return self

    def add_histogram(self, *a, **k):
        return self

    def close(self):
        pass


def _synthetic_cifar(n=1024, classes=10, seed=0):
    """Deterministic learnable image set: per-class template + noise."""
    rs = np.random.RandomState(seed)
    templates = rs.rand(classes, 32, 32, 3).astype(np.float32)
    y = rs.randint(0, classes, (n,))
    x = templates[y] + 0.25 * rs.rand(n, 32, 32, 3).astype(np.float32)
    return x, y


def run_resnet(steps: int = 60, batch: int = 256, accum: int = 4):
    """Scaled flagship recipe: ResNet-8/cifar trunk, warmup->poly LARS;
    the 256-sample update batch is reached via 4 accumulated 64-sample
    micro-batches (set_gradient_accumulation SPLITS each batch — one
    update per ``batch`` samples), the same mechanism that carries the
    recipe to its 8192 global batch on constant memory.  Returns
    per-iteration losses."""
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.models.resnet_train import make_recipe_optim

    x, y = _synthetic_cifar()
    ds = DataSet.from_arrays(x, y, batch_size=batch)
    iters_per_epoch = ds.batches_per_epoch()
    epochs = max(1, (steps + iters_per_epoch - 1) // iters_per_epoch)
    # maxLr linearly scaled from the published 3.2@8192 to the actual
    # update batch, per the README recipe
    args = SimpleNamespace(learningRate=0.32 * batch / 8192,
                           maxLr=3.2 * batch / 8192,
                           warmupEpoch=max(1, epochs // 6),
                           maxEpoch=epochs, momentum=0.9,
                           weightDecay=1e-4, optim="lars")
    model = ResNet(class_num=10, depth=8, dataset="cifar10")
    rec = _LossRecorder()
    opt = (optim.Optimizer.apply(
        model, ds, nn.ClassNLLCriterion(logits=True),
        end_trigger=optim.Trigger.max_epoch(epochs))
        .set_optim_method(make_recipe_optim(args, iters_per_epoch)))
    opt.set_gradient_accumulation(accum)
    opt.set_train_summary(rec)
    opt.optimize()
    return rec.losses[:steps]


def run_ptb():
    """Short-horizon PTB-LM checkpoint: fixed Zipf corpus, 2 epochs;
    returns {val_loss, perplexity} (ptb_train recipe machinery)."""
    from bigdl_tpu.models.ptb_train import main

    r = main(["--syntheticSize", "20000", "--vocabSize", "200",
              "-b", "16", "--numSteps", "20", "--maxEpoch", "2",
              "--hiddenSize", "64", "--embeddingSize", "32",
              "--numLayers", "1", "--dropout", "0.0"])
    return {"val_loss": float(r["val_loss"]),
            "perplexity": float(r["perplexity"])}


def _windowed(xs, w=10):
    xs = np.asarray(xs, np.float64)
    w = max(1, min(w, len(xs)))  # short series: shrink the window
    n = len(xs) // w
    return xs[: n * w].reshape(n, w).mean(axis=1)


def compare_resnet(golden, got, tol):
    """Windowed-mean trajectories must agree within rel tol; returns a
    list of human-readable failures (empty = pass)."""
    if not golden or not got:
        return ["resnet: empty loss series (golden "
                f"{len(golden)}, got {len(got)})"]
    w = max(1, min(10, len(golden), len(got)))
    g, h = _windowed(golden, w), _windowed(got, w)
    n = min(len(g), len(h))
    fails = []
    # denominator floored at the training-noise scale: once the loss
    # converges near zero (the fixture ends ~0.003), a pure relative
    # test would flag healthy bf16/fused-kernel noise as divergence
    rel = np.abs(g[:n] - h[:n]) / np.maximum(np.abs(g[:n]), 0.05)
    worst = int(np.argmax(rel))
    if rel.max() > tol:
        fails.append(f"resnet window {worst}: golden {g[worst]:.4f} vs "
                     f"{h[worst]:.4f} (rel {rel.max():.3f} > tol {tol})")
    if h[n - 1] > g[n - 1] * (1 + tol):
        fails.append(f"resnet final window {h[n-1]:.4f} above golden "
                     f"{g[n-1]:.4f} by more than {tol:.0%}")
    return fails


def main(argv=None):
    p = argparse.ArgumentParser("recipe_curve")
    p.add_argument("--record", action="store_true")
    p.add_argument("--check", action="store_true")
    p.add_argument("--leg", choices=["resnet", "ptb", "both"],
                   default="both")
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--tol", type=float, default=0.15,
                   help="relative windowed-loss tolerance (use ~0.2 on "
                        "chip: bf16 + fused-kernel numerics)")
    p.add_argument("--fixtures", default=FIXTURES)
    args = p.parse_args(argv)
    if args.record == args.check:
        p.error("pass exactly one of --record / --check")
    os.makedirs(args.fixtures, exist_ok=True)
    rc = 0

    if args.leg in ("resnet", "both"):
        path = os.path.join(args.fixtures, "recipe_resnet.json")
        losses = run_resnet(steps=args.steps)
        if args.record:
            with open(path, "w") as f:
                json.dump({"steps": args.steps, "losses": losses}, f)
            print(f"recorded {len(losses)} resnet losses -> {path}")
        else:
            with open(path) as f:
                golden = json.load(f)["losses"]
            fails = compare_resnet(golden, losses, args.tol)
            for msg in fails:
                print("FAIL", msg)
            print("resnet curve", "FAIL" if fails else
                  f"OK ({min(len(golden), len(losses))} steps, "
                  f"tol {args.tol})")
            rc |= bool(fails)

    if args.leg in ("ptb", "both"):
        path = os.path.join(args.fixtures, "recipe_ptb.json")
        got = run_ptb()
        if args.record:
            with open(path, "w") as f:
                json.dump(got, f)
            print(f"recorded ptb checkpoint -> {path}: {got}")
        else:
            with open(path) as f:
                golden = json.load(f)
            rel = abs(got["perplexity"] - golden["perplexity"]) \
                / golden["perplexity"]
            ok = rel <= args.tol
            print(f"ptb perplexity {got['perplexity']:.2f} vs golden "
                  f"{golden['perplexity']:.2f} (rel {rel:.3f}) "
                  + ("OK" if ok else "FAIL"))
            rc |= not ok
    return int(rc)


if __name__ == "__main__":
    sys.exit(main())
