# NOTE: no XLA_FLAGS here — smoke tests and benches must see 1 device.
# Multi-device shard_map integration tests spawn subprocesses that set
# --xla_force_host_platform_device_count themselves.
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# the suite runs the Pallas kernels on the CPU in interpret mode, which the
# kernels only do when asked (subprocess tests inherit the opt-in)
os.environ.setdefault("REPRO_PALLAS_INTERPRET", "1")
