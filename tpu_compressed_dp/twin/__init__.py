"""The scale-out digital twin: a calibrated per-fabric alpha/beta/gamma
cost model over the collective schedules the transports actually emit,
fitted from the repo's own BENCH/MULTICHIP acceptance artifacts.

  * :mod:`~tpu_compressed_dp.twin.model`      the cost model + forward
    payload/schedule derivation (predict any (W, pods, transport,
    method, knob) point)
  * :mod:`~tpu_compressed_dp.twin.records`    BENCH/MULTICHIP loader ->
    calibration rows
  * :mod:`~tpu_compressed_dp.twin.calibrate`  least-squares fitter with
    per-row residuals

Every module is replay-deterministic (hostlint TCDP101): fits and
predictions are pure functions of the committed artifacts.
"""

from tpu_compressed_dp.twin.calibrate import (        # noqa: F401
    Calibration, Residual, fit, load_calibration, save_calibration,
)
from tpu_compressed_dp.twin.model import (            # noqa: F401
    Collective, CostModel, FabricParams, TwinPoint,
    UncalibratedFabricError, predict_step_ms, schedule_for_point,
)
from tpu_compressed_dp.twin.records import (          # noqa: F401
    CalibRow, RecordFile, calibration_rows, discover_record_paths,
    load_record_file,
)

__all__ = [
    "Calibration", "Residual", "fit", "load_calibration",
    "save_calibration", "Collective", "CostModel", "FabricParams", "TwinPoint",
    "UncalibratedFabricError", "predict_step_ms", "schedule_for_point",
    "CalibRow", "RecordFile", "calibration_rows", "discover_record_paths",
    "load_record_file",
]
