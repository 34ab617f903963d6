"""Keep the benchmark's CPU tests off any autotune file in the user's home
(the serving program reads ``~/.cache/repro/autotune.json`` unless
``REPRO_AUTOTUNE_CACHE`` is set; an empty value turns persistence off)."""

import os

os.environ.setdefault("REPRO_AUTOTUNE_CACHE", "")
