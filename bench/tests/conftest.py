import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..",
                    "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
