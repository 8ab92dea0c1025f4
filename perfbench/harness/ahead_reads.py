"""How often the serving loop runs ahead (ISSUE 36): the units the engine
launched while another was in flight, over the units it launched, between
the window's opening and the run's end (``engine.stats()["ahead"]``:
``serve.stats_at_open`` and ``serve.stats_at_end``), in percent.
``layer_metrics/step_ahead_share.*.py`` are a line each over this.  A
program whose ``stats()`` has no ``ahead`` (an older commit) reads None,
and the harness leaves the metric out.  Says the end's counts once, on a
line ``engine_ahead``: what was discarded or dropped, and why a call
launched nothing ahead.
"""
from __future__ import annotations

import json
from typing import Optional


def step_ahead_share(run) -> Optional[float]:
    s = run["serve"] if run["job"] == "serve" else None
    end = ((s or {}).get("stats_at_end") or {}).get("ahead")
    if not end:
        return None
    print("engine_ahead: " + json.dumps(end), flush=True)
    at_open = (s.get("stats_at_open") or {}).get("ahead") or {}
    launched = end["units_launched"] - at_open.get("units_launched", 0)
    ahead = end["units_ahead"] - at_open.get("units_ahead", 0)
    return 100.0 * ahead / launched if launched > 0 else None
