"""The benchmark's yardstick: traffic generation, weights from the seed, the
plain-reference comparison, peaks and shape arithmetic, and the reductions
from records, counters and the device trace to metrics."""
