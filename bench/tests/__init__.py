import os
import sys

# the program under test, for the tests that drive it on the CPU
_SRC = os.path.join(os.path.dirname(__file__), "..", "..", "src")
if _SRC not in sys.path:
    sys.path.insert(0, os.path.abspath(_SRC))
os.environ.setdefault("REPRO_KERNEL_IMPL", "jnp")
