"""The longest the load generator's own process stood still between the
lead-in and the window's end (``loadgen.heartbeat``), in ms; 0 when it never
overslept by 100 ms. Under ``serve_cell.FREEZE_S`` or the window would have
been measured again; what is left says how unquiet the machine was."""


def read(ctx):
    win = ctx["window"]
    lead = ctx["spec"]["mix"]["lead_seconds"]
    return 1e3 * max((s["seconds"] for s in win["stalls"]
                      if -lead < s["at"] + s["seconds"] and s["at"] < win["seconds"]),
                     default=0.0)
