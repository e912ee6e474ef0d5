"""The system under test, built as a configuration file states it."""
from __future__ import annotations

from typing import Any


def engine_config(config: dict[str, Any]):
    """The program's plan tuple for one configuration (autotune off: the
    configuration states its tile)."""
    from repro.core.su3.layouts import Layout
    from repro.core.su3.plan import EngineConfig

    return EngineConfig(L=config["L"], dtype=config["dtype"],
                        layout=Layout(config["layout"]), tile=config["tile"],
                        compression=config["compression"])
