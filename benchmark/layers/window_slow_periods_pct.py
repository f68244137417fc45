"""Loop: the share of the window's log periods whose rate is more than 2%
under the rate ``tokens_per_s_per_chip`` quotes. 0 in an undisturbed run
(periods agree to 0.03% on the chip); with ``window_mean_vs_quoted_pct`` it
tells a few long stalls from many short ones. The quoted rate stands while
this stays under 75."""

SLOW = 0.98


def read(record):
    rates = record.get("period_rates") or []
    quoted = record["end_to_end"].get("tokens_per_s_per_chip")
    if not rates or not quoted:
        return None
    return 100.0 * sum(r < SLOW * quoted for r in rates) / len(rates)
