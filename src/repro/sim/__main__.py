import sys

from repro.launch.compile_cache import enable_compile_cache
from repro.sim.cli import main

enable_compile_cache()
sys.exit(main())
