import sys

from repro import compile_cache
from repro.experiments.cli import main

if __name__ == "__main__":
    compile_cache.enable()
    sys.exit(main())
