"""A gauge of the window's last save over the state's size. The gauge is
read off the summary that the program publishes when a take ends
(``tpusnap.telemetry.LAST_TAKE_SUMMARY``): the check after the window
restores and scrubs, it takes nothing, so the last take is the window's."""


def reduce(obs, gauge):
    from tpusnap import telemetry

    summary = telemetry.LAST_TAKE_SUMMARY
    if not obs["ops"] or not summary or not obs["state_bytes"]:
        return None
    value = (summary.get("gauges") or {}).get(gauge)
    return None if value is None else value / obs["state_bytes"]
