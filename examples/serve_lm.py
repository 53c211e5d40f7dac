"""Batched LM serving: prefill + decode with KV caches.

Thin wrapper over repro.launch.serve showing the serving API on a reduced
config of any assigned architecture:

    PYTHONPATH=src python examples/serve_lm.py --arch qwen2-0.5b
"""

import sys

from repro.launch.serve import main

if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main(sys.argv[1:] or ["--arch", "qwen2-0.5b", "--batch", "4",
                                   "--prompt-len", "32", "--gen", "16"]))
