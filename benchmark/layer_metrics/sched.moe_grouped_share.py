"""Share of the traced slice's matched dispatches whose expert FFNs took the
grouped form: the ``moe`` argument of ``engine/dispatch`` ("grouped" or
"dense", ``models/experts.expert_form`` on the step program's row count). A
program that writes no such argument (a family with no routed experts, a
commit from before the rule) gives no value."""
import host_spans


def read(ctx):
    tl = host_spans.timeline(ctx)
    pairs = host_spans.matched(tl) if tl else None
    forms = [a["moe"] for a, _, _ in pairs or () if "moe" in a]
    return 100.0 * forms.count("grouped") / len(forms) if forms else None
