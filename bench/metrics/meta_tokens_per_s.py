"""Meta-training throughput: tokens of the meta-batches whose steps ran in
the window (support and query rows of every agent, counted from the shapes
the traffic fed), over the whole window on the host clock, which ends when
the last step's state is ready."""


def read(run):
    return run.steps * run.tokens_per_step / run.window_s
