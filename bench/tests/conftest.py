"""The benchmark's own tests run with ``PYTHONPATH=src python -m pytest
bench/tests``, outside the repository's ``testpaths``; they import the
harness's modules by name from ``bench/``."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
