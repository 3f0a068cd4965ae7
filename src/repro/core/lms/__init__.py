from repro.core.lms.planner import (MemoryPlan, PlanRequest, SwapSchedule,
                                    TensorClass, check_schedule_invariant,
                                    plan, plan_memory, plan_serve_memory,
                                    plan_to_policy, validate_optimizer,
                                    activation_classes,
                                    kv_cache_bytes_dev, layer_flops_dev)
from repro.core.lms.policies import build_policy, policy_from_preset, tag
from repro.core.lms import offload

__all__ = ["MemoryPlan", "PlanRequest", "SwapSchedule", "TensorClass",
           "check_schedule_invariant", "plan", "plan_memory",
           "plan_serve_memory", "plan_to_policy", "validate_optimizer",
           "activation_classes", "kv_cache_bytes_dev", "layer_flops_dev",
           "build_policy", "policy_from_preset", "tag", "offload"]
