"""The multi-token-prediction module's weighted loss over the step's total,
in per cent, the mean over the window's steps, from the step's own outputs
(`mtp` times the configuration's `nextn_loss_weight`, over `total`): it
says that the module ran and was weighed."""


def read(observed):
    shares = observed.get("mtp_loss_shares")
    return 100.0 * sum(shares) / len(shares) if shares else None
