"""select_us.stencil: mean host time the launcher spent selecting a
configuration (``LaunchStats.select_s``) per launch in the window."""


def read(rec):
    launches = rec.get("launches") or []
    if not launches:
        return None
    return 1e6 * sum(s["select_s"] for s in launches) / len(launches)
