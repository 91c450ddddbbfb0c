"""L3 shell + client: the share of the first worker's window that is not
the device time of its micro-steps' programs: window minus micro-steps
times ``compute_ms_p50`` (device trace), over the window.  It is what
the exchange and the host's own work leave the chip waiting for."""


def read(run):
    compute_ms = run["reduction"].get("step_module_ms_p50")
    first = run["first_worker"]
    marks = first["chipbench"]["marks"]
    worker = first["chipbench_worker"]
    steps = sum(1 for row in worker["step_rows"]
                if row[0] >= worker["first_window_step"])
    window = marks["window_close"] - marks["window_open"]
    if compute_ms is None or not worker["rounds"] or window <= 0:
        return None
    return 100.0 * (1.0 - steps * compute_ms / 1e3 / window)
