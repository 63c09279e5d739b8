"""End-to-end driver: train a ~100M-param LM for a few hundred steps.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]

Uses the smollm-360m family at ~100M scale (trimmed depth/vocab so CPU
finishes in minutes), the deterministic synthetic pipeline, AdamW with
warmup+cosine, and the fault-tolerant runner (async checkpoints — kill and
re-run to watch it resume). Loss drops from ~ln(4096) to the structured
floor of the Markov stream.
"""
import argparse
import tempfile

from repro.configs import ARCHS
from repro.compile_cache import enable_compile_cache
from repro.launch import train as train_driver


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU-smoke scale (~4M params); default is the "
                    "~100M config for real hardware")
    args = ap.parse_args()

    ckpt = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_train_lm_")
    # ~100M params: smollm-360m trimmed (12 layers, vocab 8192)
    base = ARCHS["smollm-360m"]
    cfg = base.replace(
        name="smollm-100m", n_layers=12, vocab=8192,
        compute_dtype="float32", remat=False, max_seq=512,
    )
    batch, seq = "8", "256"
    if args.tiny:
        cfg = cfg.replace(name="smollm-tiny", n_layers=4, d_model=128,
                          n_heads=4, n_kv_heads=4, d_ff=512, vocab=2048)
        batch, seq = "8", "128"
    train_driver.ARCHS[cfg.name] = cfg   # register for the driver
    print(f"params ~= {cfg.param_count() / 1e6:.1f}M")
    summary = train_driver.main([
        "--arch", cfg.name, "--steps", str(args.steps),
        "--batch", batch, "--seq", seq, "--lr", "6e-3",
        "--ckpt-dir", ckpt, "--ckpt-every", "100",
    ])
    assert summary["final_step"] >= args.steps
    print(f"checkpoints in {ckpt} (re-run with --ckpt-dir {ckpt} to resume)")


if __name__ == "__main__":
    main()
