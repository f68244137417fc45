"""Strategy -> plan: the share of ``setup_s`` that the program's set-up
ledger names (gauge ``setup.booked_s``: the ``.self`` seconds of every
set-up phase plus the jit stages' seconds, each second once, as ``train()``
froze the sum when it entered its loop, just before the feed's first batch)
over the record's ``setup_s``. What is left is what the program cannot see from inside: the caller's own
waits for the device (warm-up steps, the reference check's execution) and
its own host work. The ledger by phase and the unbooked seconds go to
standard error. Higher is better. Moves ``setup_s``. None from a program
without the ledger."""

from benchmark import harness, program_counters


def read(record):
    booked = program_counters.value("setup.booked_s")
    if booked is None:
        return None
    setup_s = record["end_to_end"]["setup_s"]
    from autodist_tpu import telemetry
    report = telemetry.setup_report()
    phases = sorted(report["phases"].items(),
                    key=lambda item: -item[1]["self_s"])
    harness.log(
        f"set-up ledger: {booked:.2f}s booked of setup_s {setup_s:.2f} "
        f"({setup_s - booked:.2f}s unbooked); jit stages "
        f"{report['jit']['wall_s']:.2f}s; self seconds by phase: "
        + ", ".join(f"{name} {p['self_s']:.2f}" for name, p in phases)
        + f"; process age at import "
          f"{report['process_age_at_import_s']}")
    return 100.0 * booked / setup_s
