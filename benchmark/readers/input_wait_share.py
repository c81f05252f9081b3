"""The loop's own spans (``train_step`` telemetry records of the window's
steps): (queue_wait_s + h2d_s) / window.  queue_wait_s is what the loop
waited; h2d_s runs on the producer thread and overlaps the step, so the sum
is an upper bound on what the input costs."""


def read(ctx):
    loop = ctx["facts"].get("loop")
    if not loop:
        return None
    return 100.0 * (loop["queue_wait_s"] + loop["h2d_s"]) / ctx["facts"][
        "window_s"]
