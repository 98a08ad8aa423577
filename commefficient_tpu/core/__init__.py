from commefficient_tpu.core.server import server_update, validate_mode_combo
from commefficient_tpu.core.state import FedState
from commefficient_tpu.core.runtime import FedRuntime
from commefficient_tpu.core.pipeline import RoundInput, RoundPipeline
from commefficient_tpu.core.async_agg import (AsyncAggregator,
                                              staleness_weight,
                                              validate_async_combo)
from commefficient_tpu.core.preempt import (PreemptGuard, RoundWatchdog,
                                            collect_ledger_state,
                                            restore_ledger_state,
                                            with_retries)

__all__ = ["server_update", "validate_mode_combo", "FedState", "FedRuntime",
           "RoundInput", "RoundPipeline",
           "AsyncAggregator", "staleness_weight", "validate_async_combo",
           "PreemptGuard", "RoundWatchdog", "with_retries",
           "collect_ledger_state", "restore_ledger_state"]
