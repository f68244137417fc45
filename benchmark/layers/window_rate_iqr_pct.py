"""Loop: the spread of the rate inside one run. Quartile distance of the
per-log-period rates over their median, in percent (quartiles interpolated
inside the data, so that the few periods a traced run of a long-step cell
has before the profiler starts still give a number). A plane that slows
many periods shows here before it moves the run's rate."""

import statistics


def read(record):
    rates = record.get("period_rates") or []
    if len(rates) < 2:
        return None
    q1, _, q3 = statistics.quantiles(rates, n=4, method="inclusive")
    return 100.0 * (q3 - q1) / statistics.median(rates)
