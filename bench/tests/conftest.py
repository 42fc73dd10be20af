"""Puts the checkout's root (for ``bench``) and ``src`` (for the program)
on the path; the benchmark's tests run on the CPU at small sizes."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
