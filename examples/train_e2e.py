"""End-to-end training driver: a granite-family model trained for a few
hundred steps on the synthetic pipeline, with checkpointing and a mid-run
injected host failure (restart + replay, loss continuous).

Default is a ~20M-param model sized for this CPU container; pass
``--hundred-m`` for the ~100M configuration (same code path, longer run).

    PYTHONPATH=src python examples/train_e2e.py [--steps 200] [--hundred-m]
"""
import sys

from repro.compile_cache import use_compile_cache
from repro.launch import train

if __name__ == "__main__":
    use_compile_cache()
    argv = sys.argv[1:]
    hundred = "--hundred-m" in argv
    argv = [a for a in argv if a != "--hundred-m"]
    if hundred:
        dims = ["--layers", "12", "--d-model", "768", "--d-ff", "2688",
                "--vocab", "4096"]
    else:
        dims = ["--layers", "6", "--d-model", "384", "--d-ff", "1344",
                "--vocab", "2048"]
    sys.argv = (["train"] + dims +
                ["--arch", "granite-3-8b", "--steps", "200",
                 "--batch", "4", "--seq", "128",
                 "--ckpt-every", "50", "--fail-at", "120",
                 "--ckpt-dir", "/tmp/repro_e2e_ckpt"] + argv)
    train.main()
