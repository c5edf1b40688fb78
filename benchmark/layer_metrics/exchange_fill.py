"""`exchange_fill`: of the slots the generic exchange plan's rounds carry,
the share that are real ghost values, in percent: `exchange.plan.slots /
exchange.plan.padded_slots`, both counted by the program where a staged
operator takes its plan. Every round of the plan is padded to its longest
edge (`P x R x L` slots in all), so 100 / fill is by how much the gathers,
the wire and the scatters of an exchange exceed the ghosts. Source:
program_counter. None where the operator has a box plan or one part, or
the program has no such counters. Like the readers beside it, it speaks
only in a run whose trace holds device ops."""
from benchmark.layer_metrics._traced import traced_stretch


def fill(counters: dict):
    padded = counters.get("exchange.plan.padded_slots", 0)
    if not padded:
        return None
    return 100.0 * counters.get("exchange.plan.slots", 0) / padded


def reduce(run):
    if traced_stretch(run) is None:
        return None
    from partitionedarrays_jl_tpu import telemetry

    return fill(telemetry.counters("exchange.plan"))
