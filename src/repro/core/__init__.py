from repro.core.cache_policies import POLICIES, LearnedPolicy, make_policy
from repro.core.costmodel import CostModel, HardwareProfile, ModelBytes
from repro.core.expert_cache import ExpertCache
from repro.core.expert_store import ExpertStore
from repro.core.learned import (LearnedModel, evaluate_recall,
                                train_from_trace)
from repro.core.memory_tiers import (SwapQueue, TieredMemoryManager,
                                     plan_hbm_split)
from repro.core.offload_engine import OffloadEngine, init_offloaded_params
from repro.core.paged_kv import PagedKVCache
from repro.core.prefetch import (LearnedPredictor, MarkovPredictor,
                                 SpeculativePrefetcher)
from repro.core.trace import StepTrace, TierEvent, TraceRecorder
from repro.core.transfer_engine import Transfer, TransferEngine

__all__ = [
    "POLICIES", "make_policy", "CostModel", "HardwareProfile", "ModelBytes",
    "ExpertCache", "ExpertStore", "LearnedModel", "LearnedPolicy",
    "LearnedPredictor", "OffloadEngine", "MarkovPredictor",
    "PagedKVCache", "SpeculativePrefetcher", "StepTrace", "SwapQueue",
    "TierEvent", "TieredMemoryManager", "TraceRecorder", "Transfer",
    "TransferEngine", "evaluate_recall", "init_offloaded_params",
    "plan_hbm_split", "train_from_trace",
]
