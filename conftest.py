import os
import sys

# Tests see the default single CPU device (the dry-run sets its own flags
# in a separate process).  Keep compilation single-threaded and quiet.
# At the root, so that it loads before bench/tests/conftest.py, whose test
# files import it as ``conftest``.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "src"))
