"""Percentiles, spreads and window accounting (pure Python, no JAX)."""
import statistics


def percentile(values, q):
    """q-th percentile (0..100) by linear interpolation between order
    statistics; None for an empty sample."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def spread(values):
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(n=4)``): the builder's measure."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def rate_over_window(event_times, t_open, t_close):
    """Events stamped inside [t_open, t_close) over the WHOLE window: a
    stall inside the window lowers the rate, it does not shorten the
    denominator."""
    n = sum(1 for t in event_times if t_open <= t < t_close)
    return n / (t_close - t_open)
