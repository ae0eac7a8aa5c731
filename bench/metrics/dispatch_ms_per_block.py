"""Host wall time of one ``FedTrainer.run_block`` call (trainer and engine
dispatch), from the harness's ``bench.run_block`` spans in the trace."""


def read(facts):
    spans = [e - s for s, e, name in facts["trace"]["spans"]
             if name == "bench.run_block"]
    return sum(spans) / len(spans) * 1e-6 if spans else None
