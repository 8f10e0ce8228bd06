"""Serve a small model with batched requests through the decode engine
(continuous batching over fixed cache slots).

  PYTHONPATH=src python examples/serve_lm.py --arch gemma2-27b --requests 8
"""

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import main

if __name__ == "__main__":
    enable_compile_cache()
    main()
