import os
import sys

# smoke tests and benches see 1 CPU device (multi-device tests run in
# subprocesses that set their own XLA_FLAGS) — ensure src is importable
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
