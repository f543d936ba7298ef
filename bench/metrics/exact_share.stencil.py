"""exact_share.stencil: share of the window's launches that wisdom served
at tier ``exact`` (``LaunchStats.tier``), in percent."""


def read(rec):
    launches = rec.get("launches") or []
    if not launches:
        return None
    return 100.0 * sum(s["tier"] == "exact" for s in launches) / len(launches)
