# repro.obs — observability for the kernel/link/NoC/DSE stack
# (DESIGN.md §14):
#   metrics.py  - counter/gauge/histogram registry + scoped collect()
#   trace.py    - span API emitting Chrome/Perfetto trace-event JSON
#   probes.py   - the sink behind repro._obs_hooks: probe vocabulary,
#                 collect()/tracing()/profiling() activation
#   report.py   - per-link BT tables, top-N hottest links, CSV/JSON dumps
#   activity.py - wire-level switching-activity profiles (DESIGN.md §15)
#   saif.py     - SAIF / VCD export of measured activity for EDA flows
#   capture.py  - real-model traffic capture: taps on the model zoo
#                 recording int8 wire streams for the BT stack
#                 (DESIGN.md §16)
#
# Disabled by default with provably zero cost: production modules import
# only repro._obs_hooks (a None-test per probe, fired OUTSIDE any traced
# computation), so importing or activating this package leaves every
# kernel entry point's traced jaxpr byte-identical (tests/test_obs.py).
from .activity import (
    ActivityProfile,
    link_profiles,
    profile_from_arrays,
    profiles_from_noc,
    wire_name,
    wire_records,
    write_wires_csv,
)
from .capture import (
    TAP_SCENARIOS,
    CapturedStream,
    MoeRoute,
    CaptureSession,
    capture,
    capture_lenet_conv,
    capture_moe_dispatch,
    capture_serve_decode,
    capture_train_step,
    load_session,
    save_session,
)
from .metrics import Counter, Gauge, Histogram, Registry, registry_from_dict
from .probes import (
    PROBE_KINDS,
    active_registries,
    active_tracers,
    collect,
    profiling,
    tracing,
)
from .report import (
    activity_table,
    format_links,
    format_scenarios,
    link_table,
    metrics_dict,
    read_metrics_json,
    scenario_table,
    top_links,
    top_wires,
    write_activity_csv,
    write_links_csv,
    write_metrics_json,
    write_scenarios_csv,
    write_scenarios_json,
)
from .saif import parse_saif, write_saif, write_vcd
from .trace import Tracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "registry_from_dict",
    "Tracer",
    "PROBE_KINDS",
    "collect",
    "tracing",
    "profiling",
    "active_registries",
    "active_tracers",
    "link_table",
    "top_links",
    "format_links",
    "write_links_csv",
    "activity_table",
    "top_wires",
    "write_activity_csv",
    "scenario_table",
    "format_scenarios",
    "write_scenarios_csv",
    "write_scenarios_json",
    "TAP_SCENARIOS",
    "CapturedStream",
    "MoeRoute",
    "CaptureSession",
    "capture",
    "capture_serve_decode",
    "capture_train_step",
    "capture_moe_dispatch",
    "capture_lenet_conv",
    "save_session",
    "load_session",
    "metrics_dict",
    "write_metrics_json",
    "read_metrics_json",
    "ActivityProfile",
    "profile_from_arrays",
    "link_profiles",
    "profiles_from_noc",
    "wire_name",
    "wire_records",
    "write_wires_csv",
    "parse_saif",
    "write_saif",
    "write_vcd",
]
