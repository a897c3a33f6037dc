"""Batched serving with continuous batching (deliverable b, serving kind).

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
from repro.launch import serve as serve_mod


def main():
    serve_mod.main([
        "--arch", "smollm-360m", "--reduced",
        "--requests", "6", "--max-new", "12", "--max-batch", "3",
    ])


if __name__ == "__main__":
    main()
