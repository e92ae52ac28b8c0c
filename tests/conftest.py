import os
import sys

# Tests see ONE cpu device (the dry-run sets 512 itself, in its own process).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

