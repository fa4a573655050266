"""Batched serving demo across architecture families: prefill + decode
with per-family caches (KV ring buffer / SSM state / RG-LRU state).

    PYTHONPATH=src python examples/serve_llm.py
"""
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def main():
    # one child at a time, platform from the caller's environment
    env = {**os.environ, "PYTHONPATH": SRC}
    for arch in ("stablelm_1_6b", "mamba2_1_3b", "recurrentgemma_2b",
                 "mixtral_8x22b"):
        print(f"== {arch} (smoke config)")
        subprocess.run(
            [sys.executable, "-m", "repro.launch.serve", "--arch", arch,
             "--smoke", "--batch", "4", "--prompt-len", "16",
             "--tokens", "16"],
            env=env, check=True)


if __name__ == "__main__":
    main()
