from .cec_router import CECRouter
from .fleet import FleetView, RouterFleet
from .traffic import (TrafficTrace, diurnal_trace, flash_crowd_trace,
                      named_traces, poisson_trace, scenario_base_demand)

__all__ = ["CECRouter", "RouterFleet", "FleetView", "TrafficTrace",
           "poisson_trace", "diurnal_trace", "flash_crowd_trace",
           "named_traces", "scenario_base_demand"]
