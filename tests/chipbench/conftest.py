"""The benchmark's own tests (CPU only). They run under the repo's tier-1
command; nothing here computes or prints a device metric."""

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
