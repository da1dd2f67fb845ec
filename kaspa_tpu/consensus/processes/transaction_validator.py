"""Transaction validation (reference: consensus/src/processes/transaction_validator/).

- in-isolation checks (tx_validation_in_isolation.rs): counts, duplicate
  outpoints, script length limits, value ranges
- KIP-9 mass commitment checks against the contextual mass calculator
  (consensus/mass.py)
- header-context checks (tx_validation_in_header_context.rs): lock time
- UTXO-context checks (tx_validation_in_utxo_context.rs): maturity, input
  amounts, fee, sequence locks, script checks

Script checks are *collected* into a BatchScriptChecker (TPU batch) rather
than executed per input — the deferred-dispatch twist on the reference's
rayon check_scripts_par_iter (the "TPU offload point", SURVEY.md §2.5).
"""

from __future__ import annotations

from kaspa_tpu.consensus.mass import MassCalculator
from kaspa_tpu.consensus.model import SUBNETWORK_ID_NATIVE, Transaction
from kaspa_tpu.consensus.params import Params
from kaspa_tpu.txscript.batch import BatchScriptChecker
from kaspa_tpu.txscript.caches import SigCache, VerdictMemo

MAX_SOMPI = 29_000_000_000 * 100_000_000  # constants.rs MAX_SOMPI
SEQUENCE_LOCK_TIME_MASK = 0x00000000FFFFFFFF
SEQUENCE_LOCK_TIME_DISABLED = 1 << 63
LOCK_TIME_THRESHOLD = 500_000_000_000  # tx_validation_in_header_context


class TxRuleError(Exception):
    pass


FLAG_FULL = "full"
FLAG_SKIP_SCRIPTS = "skip_scripts"
FLAG_SKIP_MASS = "skip_mass"


class TransactionValidator:
    def __init__(self, params: Params, sig_cache: SigCache | None = None, vm_fallback=None):
        self.params = params
        self.coinbase_maturity = params.coinbase_maturity
        self.sig_cache = sig_cache if sig_cache is not None else SigCache()
        # the script-verdict memo over it (txscript/batch.py): the same bounded
        # map at the same size, keyed by a transaction over its spent outputs,
        # whose running epoch `Consensus._resolve_virtual` closes
        self.tx_memo = VerdictMemo()
        self.mass_calculator = MassCalculator.from_params(params)
        if vm_fallback is None:
            # nonstandard scripts run through the host VM with the shared
            # cache; Toccata activation (by the block's DAA score) selects
            # the engine flags + metering regime
            # (tx_validation_in_utxo_context.rs:171-172)
            from kaspa_tpu.txscript import vm as _vm
            from kaspa_tpu.txscript.resource_meter import RuntimeScriptUnitMeter, RuntimeSigOpCounter

            def vm_fallback(tx, entries, idx, reused, pov_daa_score=None, seq_commit_accessor=None, _cache=self.sig_cache):
                active = pov_daa_score is not None and params.toccata_active(pov_daa_score)
                flags = _vm.EngineFlags(covenants_enabled=active)
                commit = tx.inputs[idx].compute_commit
                if active:
                    sigop_units = params.mass_per_sig_op * 100  # Gram -> script units
                    budget = commit.compute_budget() or 0
                    meter = RuntimeScriptUnitMeter(sigop_units, budget * 10_000)  # SCRIPT_UNITS_PER_COMPUTE_BUDGET_UNIT
                else:
                    # pre-Toccata regime (lib.rs:545): executed sig ops may
                    # not exceed the input's committed sig-op count
                    meter = RuntimeSigOpCounter(commit.sig_op_count() or 0)
                engine = _vm.TxScriptEngine(
                    tx, entries, idx, reused, _cache, flags=flags, meter=meter,
                    seq_commit_accessor=seq_commit_accessor if active else None,
                )
                engine.execute()

        self.vm_fallback = vm_fallback

    def new_checker(self, traffic_class: str | None = None, block_path: bool = False) -> BatchScriptChecker:
        """``block_path``: consensus collecting the transactions of blocks,
        whose memo entries the running epoch holds (``BatchScriptChecker``)."""
        return BatchScriptChecker(
            self.sig_cache, self.vm_fallback, traffic_class=traffic_class, tx_memo=self.tx_memo, block_path=block_path
        )

    # --- in isolation (tx_validation_in_isolation.rs) ---

    def validate_tx_in_isolation(self, tx: Transaction) -> None:
        if not tx.is_coinbase():
            if len(tx.inputs) == 0:
                raise TxRuleError("transaction has no inputs")
            if len(tx.inputs) > self.params.max_tx_inputs:
                raise TxRuleError(f"too many inputs {len(tx.inputs)}")
            for inp in tx.inputs:
                if len(inp.signature_script) > self.params.max_signature_script_len:
                    raise TxRuleError("signature script too long")
        if len(tx.outputs) > self.params.max_tx_outputs:
            raise TxRuleError(f"too many outputs {len(tx.outputs)}")
        total = 0
        for out in tx.outputs:
            if out.value == 0:
                raise TxRuleError("zero output value")
            if out.value > MAX_SOMPI:
                raise TxRuleError("output value too high")
            total += out.value
            if total > MAX_SOMPI:
                raise TxRuleError("outputs total overflow")
            if len(out.script_public_key.script) > self.params.max_script_public_key_len:
                raise TxRuleError("script public key too long")
        seen = set()
        for inp in tx.inputs:
            if inp.previous_outpoint in seen:
                raise TxRuleError("duplicate outpoint")
            seen.add(inp.previous_outpoint)
        if tx.subnetwork_id == SUBNETWORK_ID_NATIVE and tx.gas > 0:
            raise TxRuleError("gas in native subnetwork")

    # --- header context (lock time) ---

    def validate_tx_in_header_context(self, tx: Transaction, ctx_daa_score: int, ctx_past_median_time: int) -> None:
        if tx.lock_time == 0:
            return
        if tx.lock_time < LOCK_TIME_THRESHOLD:
            block_or_time = ctx_daa_score  # interpreted as DAA score
        else:
            block_or_time = ctx_past_median_time
        # strict <: equality is NOT finalized (tx_validation_in_header_context.rs:79)
        if tx.lock_time < block_or_time:
            return
        # lock time hasn't occurred: every input must have max sequence
        if any(inp.sequence != (1 << 64) - 1 for inp in tx.inputs):
            raise TxRuleError("tx is not finalized")

    # --- utxo context (tx_validation_in_utxo_context.rs) ---

    def validate_populated_transaction_and_get_fee(
        self,
        tx: Transaction,
        entries: list,
        pov_daa_score: int,
        flags: str = FLAG_FULL,
        checker: BatchScriptChecker | None = None,
        token: int | None = None,
        seq_commit_accessor=None,
    ) -> int:
        self._check_coinbase_maturity(tx, entries, pov_daa_score)
        total_in = self._check_input_amounts(entries)
        total_out = self._check_output_values(tx, total_in)
        fee = total_in - total_out
        if flags != FLAG_SKIP_MASS:
            self._check_mass_commitment(tx, entries)
        self._check_sequence_lock(tx, entries, pov_daa_score)
        if flags in (FLAG_FULL, FLAG_SKIP_MASS):
            assert checker is not None and token is not None, "script checks need a batch checker"
            checker.collect_tx(token, tx, entries, pov_daa_score=pov_daa_score, seq_commit_accessor=seq_commit_accessor)
        return fee

    def _check_mass_commitment(self, tx, entries):
        """tx_validation_in_utxo_context.rs check_mass_commitment: the miner-
        committed storage mass must equal the KIP-9 contextual mass."""
        calculated = self.mass_calculator.calc_contextual_masses(tx, entries)
        if calculated is None:
            raise TxRuleError("mass incomputable")
        if tx.storage_mass != calculated:
            raise TxRuleError(f"wrong mass commitment: committed {tx.storage_mass}, calculated {calculated}")

    def _check_coinbase_maturity(self, tx, entries, pov_daa_score):
        for i, (inp, entry) in enumerate(zip(tx.inputs, entries)):
            if entry.is_coinbase and entry.block_daa_score + self.coinbase_maturity > pov_daa_score:
                raise TxRuleError(
                    f"immature coinbase spend at input {i}: utxo daa {entry.block_daa_score} pov {pov_daa_score}"
                )

    def _check_input_amounts(self, entries) -> int:
        total = 0
        for entry in entries:
            total += entry.amount
            if total > MAX_SOMPI:
                raise TxRuleError("input amount too high")
        return total

    def _check_output_values(self, tx, total_in) -> int:
        total_out = sum(out.value for out in tx.outputs)
        if total_in < total_out:
            raise TxRuleError(f"spend too high {total_out} > {total_in}")
        return total_out

    def _check_sequence_lock(self, tx, entries, pov_daa_score):
        pov = pov_daa_score
        for inp, entry in zip(tx.inputs, entries):
            if inp.sequence & SEQUENCE_LOCK_TIME_DISABLED == SEQUENCE_LOCK_TIME_DISABLED:
                continue
            relative_lock = inp.sequence & SEQUENCE_LOCK_TIME_MASK
            lock_daa_score = entry.block_daa_score + relative_lock - 1
            if lock_daa_score >= pov:
                raise TxRuleError("sequence lock conditions are not met")
