"""Model FLOP/s utilisation: needed operations a token (forward plus
twice that for backward, recompute not counted; ``flops.py``) times the
traced window's tokens a second, over chips times the peak."""


def read(run, metric):
    seen = run.observed
    if 'model_flops_per_token' not in seen:
        return None
    return (100.0 * seen['model_flops_per_token'] * seen['tokens_per_s']
            / (seen['chips'] * run.peaks['flops_per_s']))
