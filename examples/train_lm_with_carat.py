"""End-to-end driver: train a (reduced) LM for a few hundred steps with the
PFS-backed input pipeline, comparing CARAT on vs off.

    PYTHONPATH=src python examples/train_lm_with_carat.py [--steps 120]
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.launch.train import main as train_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--arch", default="granite-3-2b")
    args = ap.parse_args()

    common = ["--arch", args.arch, "--steps", str(args.steps),
              "--hosts", "4", "--sample-kb", "2048"]
    print("=== run 1: CARAT input-pipeline co-tuning DISABLED ===")
    train_main(common + ["--no-carat", "--ckpt-dir", "/tmp/ck_off"])
    print("\n=== run 2: CARAT input-pipeline co-tuning ENABLED ===")
    train_main(common + ["--ckpt-dir", "/tmp/ck_on"])
    print("\nCompare the input_wait_s and pfs_MBps lines: CARAT tunes each "
          "host's PFS client online while training runs.")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
