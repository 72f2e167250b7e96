"""Mean user queries per dispatched batch over the target batch, in %."""


def read(run):
    sizes = run.report.batch_sizes
    if not sizes:
        return None
    return 100.0 * sum(sizes) / len(sizes) / run.cfg["serve"]["target_batch"]
