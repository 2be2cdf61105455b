"""usd_per_wf (USD/wf): what a completed instance is billed, at the prices
of bench/prices.json: GB·s of every attempt, one invocation per attempt,
and the egress of each payload that crosses clouds between stages."""

from harness import records


def _cloud(faas):
    return faas.split("/")[0]


def read(run):
    w = run.window
    table = records.prices()
    wf = run.cell.config["workflow"]
    faas = {f["name"]: f["faas"] for f in wf["functions"]}
    memory = {f["name"]: f.get("memory_gb") for f in wf["functions"]}
    crossing = [src for src, dst in wf["edges"]
                if _cloud(faas[src]) != _cloud(faas[dst])]
    costs = []
    for inst in w.due_in_window():
        if records.completed_ms(inst.records, w.terminal) is None:
            continue
        egress = sum(records.payload_bytes(r.result) for r in inst.records
                     if r.function in crossing and r.status == "done")
        costs.append(records.usd(inst, memory, egress, table))
    return sum(costs) / len(costs) if costs else None
