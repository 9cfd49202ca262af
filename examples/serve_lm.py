"""Batched LM serving with continuous batching (deliverable b, serving
kind): submit N requests into a slot-limited decode server; finished
sequences free slots for queued requests.

    PYTHONPATH=src python examples/serve_lm.py --arch h2o-danube-3-4b --smoke
"""
import sys

sys.path.insert(0, "src")

from repro.launch.serve import main  # noqa: E402

if __name__ == "__main__":
    main()
