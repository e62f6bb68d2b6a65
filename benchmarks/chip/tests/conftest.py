"""Puts ``benchmarks/chip`` on the path, so the tests import ``chipbench``
as ``run.py`` does."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
