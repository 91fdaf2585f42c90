import os

# Multi-device tests (sharding / pipeline / MoE) need a handful of host
# devices. NOT the 512-device production setting — that is exclusively
# launch/dryrun.py's business; 8 keeps smoke tests fast and memory small.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")


def pytest_configure(config):
    # Every other test's batches stay serial, whatever their size: only
    # tests/test_schedule_pool.py starts schedule pools, through an
    # engine's own limits.
    import sys
    from repro.core import schedpool
    schedpool.POOL_MIN_WALKS = sys.maxsize
