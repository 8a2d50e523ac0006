"""Train a small LM with the LMSFC-indexed curriculum pipeline, then kill and
resume from the checkpoint — exercising train_step, AdamW, the indexed data
pipeline, checkpoint/restart, and the FT supervisor.

    PYTHONPATH=src python examples/train_lm_indexed.py [--steps 30]
"""
import argparse
import os
import shutil
import subprocess
import sys
import tempfile


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--arch", default="qwen3-4b")
    args = ap.parse_args()

    ckpt = tempfile.mkdtemp(prefix="lmsfc_ckpt_")
    base = [sys.executable, "-m", "repro.launch.train", "--arch", args.arch,
            "--reduced", "--batch", "8", "--seq", "128",
            "--ckpt-dir", ckpt, "--ckpt-every", "10"]
    # a reduced-config CPU demo: the children run on the host backend
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(root, "src"))

    half = max(10, args.steps // 2)
    print(f"phase 1: train {half} steps (checkpoint every 10)...")
    r1 = subprocess.run(base + ["--steps", str(half)], env=env,
                        cwd=root, capture_output=True, text=True)
    print(r1.stdout[-1500:])
    assert r1.returncode == 0, r1.stderr[-2000:]

    print(f"phase 2: resume from checkpoint, continue to {args.steps}...")
    r2 = subprocess.run(base + ["--steps", str(args.steps), "--resume"],
                        env=env, cwd=root, capture_output=True, text=True)
    print(r2.stdout[-1500:])
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step" in r2.stdout
    shutil.rmtree(ckpt, ignore_errors=True)
    print("checkpoint/restart round-trip ✓")


if __name__ == "__main__":
    main()
