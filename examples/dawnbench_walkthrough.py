"""Narrative walkthrough of the DAWNBench experiment progression.

The role of the reference's ``CIFAR10/experiments.ipynb`` (VERDICT r4
missing #4): the story of the paper's CIFAR protocol as a runnable script —
each stage prints what it is about to show, runs it through the SAME harness
entry points the real experiments use, and summarises what the numbers mean.
Scaled down (synthetic data, few epochs) so it completes in minutes on CPU;
every stage names the full-protocol command that produces the recorded
artifact in ``benchmarks/``.

    python examples/dawnbench_walkthrough.py            # CPU-friendly
    python examples/dawnbench_walkthrough.py --full     # the real protocol
                                                        # (chip, ~5 min/run)
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax


def stage(title, full_cmd):
    print(f"\n{'=' * 72}\n## {title}\n"
          f"   full protocol: {full_cmd}\n{'=' * 72}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="run the real 24/40-epoch protocol instead of the "
                         "8-epoch narrative scale")
    args = ap.parse_args()
    from tpu_compressed_dp.harness import dawn

    # narrative scale: the EASY synthetic set (class-colour blobs) on a
    # quarter-width net, where 6 epochs visibly learn (dense saturates,
    # compressed methods show their EF warm-up lag) in ~1 min on a chip
    # and a few minutes on a laptop CPU; runs on whatever backend jax
    # lands on (like examples/quickstart.py).  --full switches to the
    # full-width net + the non-saturating --synthetic_hard benchmark the
    # recorded grids use.
    if args.full:
        common = ["--synthetic_hard", "--log_dir", ""]
    else:
        common = ["--synthetic", "--synthetic_n", "1024", "--epochs", "6",
                  "--batch_size", "256", "--channels_scale", "0.25",
                  "--log_dir", ""]

    # ------------------------------------------------------------------
    stage("1. The dense baseline — the DAWNBench recipe itself",
          "python -m tpu_compressed_dp.harness.dawn  (94% CIFAR-10; with a "
          "real dataset use tools/reproduce_headline.py)")
    print("ResNet-9, bs 512, lr triangle peaking 0.4 at epoch 5 — the\n"
          "reference's dawn.py protocol verbatim.  On --synthetic_hard the\n"
          "24-epoch run lands ~0.96 test accuracy (benchmarks/convergence_*).")
    dense = dawn.main(common + ["--momentum", "0.9"])
    print(f"-> test acc {dense['test acc']:.4f}")

    # ------------------------------------------------------------------
    stage("2. Layer-wise Top-K — the paper's first compression claim",
          "tools/convergence_sweep.py --only topk-lw-1%  "
          "(recorded: 0.9609 vs dense 0.9619, convergence_r4.tsv)")
    print("Keep the top 1% of each layer's gradient by magnitude, with\n"
          "error feedback accumulating what was dropped.  Same protocol,\n"
          "99% fewer coordinates synced.")
    topk = dawn.main(common + ["--momentum", "0.9", "--compress", "layerwise",
                               "--method", "topk", "--ratio", "0.01",
                               "--error_feedback"])
    print(f"-> test acc {topk['test acc']:.4f}  "
          f"(sent fraction {topk.get('sent frac', 1.0):.4f})")

    # ------------------------------------------------------------------
    stage("3. Wire mode — actually-small payloads, not simulation",
          "tools/convergence_sweep.py --only topk-em-1%-wire  "
          "(recorded: 0.9619 — parity with simulate)")
    print("The reference SIMULATES compression (dense all-reduce of a\n"
          "zeroed tensor); mode='wire' ships the real packed payload\n"
          "(values + indices over all_gather) and bills measured bytes —\n"
          "NIC-validated to ~3% in benchmarks/transport_validation_r5.tsv.")
    wire = dawn.main(common + ["--momentum", "0.9", "--compress",
                               "entiremodel", "--method", "topk", "--ratio",
                               "0.01", "--error_feedback", "--mode", "wire"])
    print(f"-> test acc {wire['test acc']:.4f}  "
          f"(wire fraction {wire.get('wire frac', 1.0):.4f} of dense bits)")

    # ------------------------------------------------------------------
    stage("4. The operator the paper found fragile — and what fixes it",
          "tools/convergence_sweep.py --only adaptive-lw-EF-40ep  "
          "(recorded: 0.9624 = dense parity at ~1.1% sent)")
    print("Adaptive threshold (keep |g| >= max|g|/2 per layer) sends ~0.02%\n"
          "and stalls without help (0.485 in 24 epochs).  Error feedback\n"
          "turns it into a dense-parity method: the residual accumulates\n"
          "until it crosses the bar, self-regulating density to ~1%.")
    # the recorded dense-parity row is the 40-epoch recipe (the harness's
    # 40-epoch rule covers randomk/thresholdv but not adaptive_threshold)
    ada = dawn.main(common + ["--momentum", "0.9", "--compress", "layerwise",
                              "--method", "adaptive_threshold",
                              "--error_feedback"]
                    + (["--epochs", "40"] if args.full else []))
    print(f"-> test acc {ada['test acc']:.4f}  "
          f"(sent fraction {ada.get('sent frac', 0.0):.5f})")

    # ------------------------------------------------------------------
    print(f"\n{'=' * 72}\n## Where this goes next\n{'=' * 72}")
    print("* multi-chip projection: benchmarks/time_to_accuracy_r5.tsv —\n"
          "  compression pays where the link is slow (DCN-class, stable\n"
          "  across latency/overlap assumptions: tta_sensitivity_r5.tsv);\n"
          "* the wire fast path: Block-Top-K;\n"
          "* the LM/stretch side: harness.lm --preset llama3_8b;\n"
          "* chip numbers: PERF.md and PERF_LEDGER.jsonl.")
    summary = {
        "dense": dense["test acc"], "topk_lw_1pct": topk["test acc"],
        "wire_topk_1pct": wire["test acc"], "adaptive_EF": ada["test acc"],
    }
    print("\nwalkthrough summary:", summary)
    return summary


if __name__ == "__main__":
    main()
