import os
import sys

# the harness's tests run on the CPU, at tiny sizes, with the checkout's
# root importable (the benchmark runs as ``benchmark.*`` from there)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
