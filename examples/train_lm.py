"""End-to-end LM training with the full production substrate:
deterministic pipeline, AdamW, async atomic checkpoints, watchdog,
int8-compressed gradients.  (CPU-sized; --preset lm100m on accelerators.)

  PYTHONPATH=src python examples/train_lm.py
"""
from repro.compile_cache import use_compile_cache
from repro.launch.train import PRESETS, train

use_compile_cache()

params, losses = train(
    PRESETS["lm_tiny"], steps=30, batch=4, seq=64,
    ckpt_dir="/tmp/repro_lm_ckpt", ckpt_every=10,
    compress=True, watchdog_s=300.0, log_every=5)
print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f} over {len(losses)} steps")
assert losses[-1] < losses[0], "loss should decrease"
