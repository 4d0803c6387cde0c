from repro.serving.kv_cache import (BlockAllocator, PagedKVCache,  # noqa: F401
                                    PagedKVConfig)
from repro.serving.paged_engine import (PagedBatchResult,  # noqa: F401
                                        PagedDecodeState, PagedEngine,
                                        PagedEngineConfig, kv_block_bytes)
from repro.serving.prefix_cache import (PrefixCache, PrefixMatch,  # noqa: F401
                                        RadixBlockTree)
from repro.serving.speculative import (Drafter, ModelDrafter,  # noqa: F401
                                       NGramDrafter, get_drafter)
from repro.serving.cluster import (Autoscaler, AutoscalerConfig,  # noqa: F401
                                   FaultEvent, FaultPlan, Fleet,
                                   FleetAutoscaler, FleetAutoscalerConfig,
                                   HardwareProfile, HealthConfig,
                                   ModelPoolSpec, NoCompatiblePoolError,
                                   Replica, RetryConfig, Router, RouterConfig)
from repro.serving.simulator import (ClusterSimResult,  # noqa: F401
                                     ContinuousSimResult, LatencyModel,
                                     SimResult, morphling_deploy_overhead,
                                     paper_cluster, replicated_cluster,
                                     simulate, simulate_cluster,
                                     simulate_continuous)
