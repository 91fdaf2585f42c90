"""Batched serving example: prefill + decode over a KV cache for several
concurrent requests (reduced llama config).

  PYTHONPATH=src python examples/serve_lm.py
"""
from repro.launch.serve import main

if __name__ == "__main__":
    main(["--arch", "llama3.2-3b", "--smoke", "--requests", "4",
          "--max-new", "16", "--prompt-len", "32"])
