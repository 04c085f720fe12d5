"""End-to-end production-style driver (deliverable b): trains a dense LM
with FedAvg/local-SGD rounds on a (pod, data, model) mesh — 8 forced host
devices standing in for 2 pods. A few hundred optimizer steps by default:
75 rounds x 4 local steps = 300 steps.

    PYTHONPATH=src python examples/production_local_sgd.py           # ~20M
    PYTHONPATH=src python examples/production_local_sgd.py --large   # ~110M

Compare against per-step-synced FedSGD (same total steps, Hx the pod-axis
collective traffic):

    PYTHONPATH=src python examples/production_local_sgd.py --algo fedsgd
"""
import sys

from repro.launch.train import main
from repro.utils.compile_cache import use_compile_cache

if __name__ == "__main__":
    use_compile_cache()
    argv = sys.argv[1:]
    extra = []
    if "--large" in argv:
        argv.remove("--large")
        # ~110M params: 12 x d768 (heads 12/kv 4) — a few hundred steps of
        # this runs in hours on this 1-core CPU container; the default demo
        # size shows the same system behaviour in minutes.
        extra = ["--d-model", "768", "--n-layers", "12"]
    main(["--demo", "--rounds", "75", "--local-steps", "4"] + extra + argv)
