"""Icicle watching its own training cluster: train a small model with
checkpointing while the monitor indexes the checkpoint directory's file
events; then drive checkpoint GC decisions from the index.

    PYTHONPATH=src python examples/monitor_training_fs.py
"""
import sys
import tempfile

sys.path.insert(0, "src")


from repro.launch.compile_cache import configure_compile_cache
from repro.launch.train import train


def main():
    configure_compile_cache()
    with tempfile.TemporaryDirectory() as d:
        out = train("olmo-1b", steps=12, reduced=True, global_batch=2,
                    seq_len=64, ckpt_dir=d, ckpt_every=4, log_every=4,
                    monitor=True)
        print(f"final loss: {out['final_loss']:.4f}")
        # crash + resume: the index-discovered latest checkpoint drives it
        out2 = train("olmo-1b", steps=16, reduced=True, global_batch=2,
                     seq_len=64, ckpt_dir=d, ckpt_every=4, log_every=4,
                     monitor=True)
        print(f"resumed run final loss: {out2['final_loss']:.4f}")
    print("OK")


if __name__ == "__main__":
    main()
