"""Instrumentation + collection substrate for AutoAnalyzer (paper §4)."""
from .attributes import (DEVICE_PEAKS, DevicePeaks, device_peaks,
                         dominant_term, region_attributes, roofline_terms,
                         HBM_BW, LINK_BW, PEAK_FLOPS)
from .costs import (AnalyticCosts, CostProvider, HloCosts, ModuleCoverage,
                    PROVIDER_KEYS, boundedness_ratios)
from .instrument import Instrumenter, build_step_tree
from .recorder import (ATTR_FIELDS, LOCATE_FIELDS, PAPER_BYTES_PER_CELL,
                       RECORD_DTYPE, RegionRecorder, WindowSnapshot,
                       WIRE_VERSION, WireFormatError, WireSkewError,
                       merge_snapshots)
from .schema import (AttributeField, AttributeSchema, PAPER_SCHEMA,
                     TPU_SCHEMA, get_schema, list_schemas, register_schema)
from .straggler import (StragglerVerdict, detect, detect_timeline,
                        persistent_stragglers, rebalance_weights)
