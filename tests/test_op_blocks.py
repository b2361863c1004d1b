"""The train step's ops by model block (``telemetry/op_blocks.py``): the
rule on written ``op_name``s, the table of four families' compiled train
steps on the CPU, the row ``JaxTrain`` writes, and the join of a trace's
events with the table."""

import jax
import jax.numpy as jnp
import pytest

from mlcomp_tpu.telemetry import op_blocks
from mlcomp_tpu.telemetry.op_blocks import block_of, scopes

LM = 'jit(step_in_context)/jit(main)'


# ------------------------------------------------------------ the rule
@pytest.mark.parametrize('op_name,block', [
    # a forward op and its backward land in the op's own block
    (f'{LM}/jvp(TransformerLM)/while/body/closed_call/layers/attn/qkv/'
     'dot_general', 'attention'),
    (f'{LM}/transpose(jvp(TransformerLM))/while/body/closed_call/'
     'checkpoint/layers/attn/qkv/transpose', 'attention'),
    # remat's recomputation of a layer
    (f'{LM}/transpose(jvp(Lfm2MoeLM))/jvp(Lfm2MoeLM)/checkpoint/'
     'rematted_computation/layer_1/moe/top_k', 'moe_routing'),
    (f'{LM}/transpose(jvp(DeepseekV3LM))/jvp(DeepseekV3LM)/checkpoint/'
     'rematted_computation/layer_2/moe/shared/wi_gate/dot_general', 'mlp'),
    (f'{LM}/jvp(Qwen3NextLM)/layer_3/moe/expert_matmul/gmm', 'moe_experts'),
    (f'{LM}/transpose(jvp(Qwen3NextLM))/periods/while/body/closed_call/'
     'checkpoint/layer_0/linear_attn/gated_delta_fwd', 'mixer'),
    (f'{LM}/jvp(Lfm2MoeLM)/layer_0/conv/short_conv_fwd', 'mixer'),
    (f'{LM}/jvp(Qwen3NextLM)/layer_3/full_attn/gqa_attn/reshape',
     'attention'),
    (f'{LM}/jvp(DeepseekV3LM)/layer_0/mlp/wi_up/dot_general', 'mlp'),
    (f'{LM}/optimizer/mul', 'optimizer'),
    # the loss, the final norm, the head and the LM's own ops: the
    # embedding's lookup and its gradient, a tied head
    (f'{LM}/transpose(jvp(loss))/jit(take_along_axis)/scatter-add',
     'embed_head'),
    (f'{LM}/jvp(DeepseekV3LM)/lm_head/dot_general', 'embed_head'),
    (f'{LM}/transpose(jvp(Qwen3NextLM))/norm_final/mul', 'embed_head'),
    (f'{LM}/transpose(jvp(DeepseekV3LM))/jit(_take)/scatter-add',
     'embed_head'),
    (f'{LM}/transpose(jvp(Lfm2MoeLM))/btd,vd->btv/dot_general',
     'embed_head'),
    # what is left: pre-norms, residual adds, remat's own copies, casts
    (f'{LM}/jvp(DeepseekV3LM)/layer_4/norm_ffn/mul', 'other'),
    (f'{LM}/jvp(Qwen3NextLM)/layer_1/add', 'other'),
    (f'{LM}/transpose(jvp(Lfm2MoeLM))/jvp(Lfm2MoeLM)/remat2', 'other'),
    # a scanned stack's buffers, made at the model's own level, and the
    # learned positions beside them
    (f'{LM}/transpose(jvp(TransformerLM))/broadcast_in_dim', 'other'),
    (f'{LM}/jvp(TransformerLM)/add', 'embed_head'),
    (f'{LM}/jvp(Qwen3NextLM)/jit(_take)/broadcast_in_dim', 'embed_head'),
    ('', 'other'),
])
def test_the_rule(op_name, block):
    assert block_of(op_name) == block
    assert op_blocks.is_backward(op_name) == ('transpose(' in op_name)


def test_scopes_take_the_wrappers_off():
    assert scopes('jit(step)/transpose(jvp(loss))/jvp()/div') \
        == ['step', 'loss', 'div']
    assert scopes('a/transpose(jvp(b/c))/d') == ['a', 'b', 'c', 'd']


# ------------------------------------------------- compiled train steps
TINY = {
    'transformer_lm': dict(vocab_size=128, d_model=32, n_layers=2,
                           n_heads=4, d_ff=64, max_seq_len=32),
    'qwen3_next': dict(
        vocab_size=64, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=16, linear_key_heads=2, linear_value_heads=4,
        linear_key_dim=8, linear_value_dim=8, n_experts=16, top_k=2,
        d_expert=16, d_shared=16, experts_held=8, expert_offset=4,
        delta_chunk=16),
    'lfm2_moe': dict(
        vocab_size=64, d_model=128,
        layer_types=['conv', 'full_attention', 'conv', 'conv', 'conv'],
        n_dense_layers=1, d_ff=64, n_heads=4, n_kv_heads=2, head_dim=16,
        n_experts=16, top_k=2, d_expert=16, experts_held=8,
        expert_offset=4, expert_bias_update_rate=0.001),
    'deepseek_v3': dict(
        vocab_size=64, d_model=64, n_layers=3, n_dense_layers=1, d_ff=96,
        n_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_experts=16, top_k=3,
        d_expert=24, n_shared_experts=2, experts_held=4, expert_offset=4),
}
#: the blocks each family's step has to show (the cells' per-block
#: metrics in BENCHMARK.json): qwen's ``mlp`` is its shared expert
WANT = {
    'transformer_lm': {'attention', 'mlp'},
    'qwen3_next': {'attention', 'mixer', 'moe_routing', 'moe_experts',
                   'mlp'},
    'lfm2_moe': {'attention', 'mixer', 'moe_routing', 'moe_experts',
                 'mlp'},
    'deepseek_v3': {'attention', 'moe_routing', 'moe_experts', 'mlp'},
}


def compiled_step_text(name):
    from mlcomp_tpu.models import create_model
    from mlcomp_tpu.train.loop import (
        create_train_state, loss_for_task, make_train_step)
    from mlcomp_tpu.train.optim import make_optimizer
    tokens = jnp.zeros((2, 32), jnp.int32)
    model = create_model(name, dtype='float32', remat=True, **TINY[name])
    optimizer = make_optimizer({'name': 'adamw', 'lr': 1e-3})[0]
    state = jax.eval_shape(lambda: create_train_state(
        model, optimizer, tokens, jax.random.PRNGKey(1)))
    step = make_train_step(model, optimizer, loss_for_task('lm_ce'),
                           self_supervised=True)
    return step.lower(state, tokens, None).compile().as_text()


@pytest.mark.parametrize('name', sorted(TINY))
def test_every_op_of_a_compiled_step_has_a_block(name):
    text = compiled_step_text(name)
    table = op_blocks.op_table(text)
    # the entry's instructions and the bodies' a while or call runs,
    # not what lies inside a fusion
    assert 'ROOT' not in ''.join(table)
    fused = [line.split('=')[0].strip().lstrip('%') for line in
             text.split('%fused_computation')[1].splitlines()[1:3]]
    assert not set(fused) & set(table)
    blocks = {block for _, block, _ in table.values()}
    assert blocks <= set(op_blocks.BLOCKS)
    assert WANT[name] | {'embed_head', 'optimizer', 'other'} <= blocks
    assert {backward for _, _, backward in table.values()} == {0, 1}
    # the update rule's two scopes are in the text
    names = op_blocks._OP_NAME_RE.findall(text)
    assert any('loss' in scopes(n) for n in names)
    assert any(block_of(n) == 'optimizer' for n in names)
    # each entry's shape is what the text says of its result
    some = next(n for n, row in table.items() if row[0].startswith('f32['))
    line = next(line for line in text.splitlines()
                if line.lstrip().startswith(f'%{some} = '))
    assert f'= {table[some][0]}' in line


def test_a_scanned_stack_lands_in_its_blocks():
    """OLMo's layers are a ``while``: the body's instructions are in the
    table, the ``while`` itself only encloses them."""
    text = compiled_step_text('transformer_lm')
    table = op_blocks.op_table(text)
    whiles = [n for n in table if n.startswith('while')]
    assert whiles
    inside = [n for n, (_, block, _) in table.items()
              if block in ('attention', 'mlp')]
    assert len(inside) > 4


# ------------------------------------------------------ the JaxTrain row
def test_jax_train_writes_the_row_once_a_job(session, tmp_path):
    from mlcomp_tpu.db.models import Dag, Task
    from mlcomp_tpu.db.providers import (
        DagProvider, ProjectProvider, TaskProvider,
    )
    from mlcomp_tpu.db.providers.telemetry import MetricProvider
    from mlcomp_tpu.train import JaxTrain
    from mlcomp_tpu.utils.misc import now

    class QuietStep:
        def start(self, *a, **k):
            pass

        def info(self, m):
            pass

        debug = error = info

        def end_all(self):
            pass

    def job(telemetry):
        provider = ProjectProvider(session)
        if provider.by_name('p_blocks') is None:
            provider.add_project('p_blocks')
        dag = Dag(name='d', project=provider.by_name('p_blocks').id,
                  config='', created=now(), docker_img='default')
        DagProvider(session).add(dag)
        task = Task(name='t', executor='e', dag=dag.id, status=0)
        TaskProvider(session).add(task)
        ex = JaxTrain(
            model=dict(TINY['deepseek_v3'], name='deepseek_v3',
                       dtype='float32'),
            dataset={'name': 'synthetic_lm', 'n_train': 16, 'n_valid': 4,
                     'seq_len': 16, 'vocab_size': 64},
            loss='lm_ce', batch_size=4, epochs=1, mesh={'dp': 1},
            checkpoint_dir=str(tmp_path / str(task.id)),
            checkpoint_every=0, telemetry=telemetry)
        ex.step, ex.task, ex.session = QuietStep(), task, session
        ex.dag, ex.additional_info = DagProvider(session).by_id(dag.id), {}
        ex.work()
        return MetricProvider(session).series(
            task_id=task.id, name=op_blocks.ROW).get(op_blocks.ROW, [])

    (row,) = job({'op_blocks': True})
    table = op_blocks.load_op_table(row['tags'])
    assert row['value'] == len(table) > 100
    assert row['tags']['build_s'] >= 0
    assert WANT['deepseek_v3'] | {'embed_head', 'optimizer'} <= {
        block for _, block, _ in table.values()}
    # off by default on the CPU, as its siblings
    assert job({'flush_every': 16}) == []


# ------------------------------------------------------------- the join
def ev(name, shape, start, dur, op='fusion'):
    return [f'{name} = {shape}{{1,0}} {op}(%a), metadata={{}}', start, dur]


TABLE = {'fusion.1': ['bf16[4,8]', 'mlp', 0],
         'fusion.2': ['bf16[4,8]', 'mlp', 1],
         'attn.3': ['bf16[2,8]', 'attention', 0],
         'while.4': ['(s32[])', 'other', 0],
         'add.5': ['f32[8]', 'optimizer', 0]}


def test_the_join_sums_a_step_onto_its_blocks():
    modules = [['jit_step(1)', 0, 100], ['jit_step(2)', 100, 40],
               ['jit_step(1)', 200, 100]]
    ops = []
    for at in (0, 200):
        ops += [ev('fusion.1', 'bf16[4,8]', at, 20),
                ev('fusion.2', 'bf16[4,8]', at + 20, 30),
                ev('while.4', '(s32[])', at + 50, 40, 'while'),
                ev('attn.3', 'bf16[2,8]', at + 50, 30, 'custom-call'),
                ev('add.5', 'f32[8]', at + 80, 10)]
    # the eval program's ops are no op of the train step's table
    ops.append(ev('fusion.9', 'f32[2]', 110, 20))
    out = op_blocks.block_split(modules, ops, TABLE)
    assert (out['module'], out['runs']) == ('jit_step(1)', 2)
    assert out['matched'] == 1.0
    assert out['step_ms'] == pytest.approx(100e-6)
    assert out['ms']['mlp'] == pytest.approx(50e-6)
    assert out['backward_ms']['mlp'] == pytest.approx(30e-6)
    assert out['ms']['attention'] == pytest.approx(30e-6)
    assert out['kernel_ms']['attention'] == pytest.approx(30e-6)
    assert out['ms']['optimizer'] == pytest.approx(10e-6)
    assert sum(out['ms'].values()) == pytest.approx(90e-6)
    assert out['top']['mlp'][0] == ['fusion bf16[4,8]',
                                    pytest.approx(30e-6), 1]


def test_a_shape_that_differs_is_not_matched():
    modules = [['jit_step(1)', 0, 100]]
    ops = [ev('fusion.1', 'bf16[4,8]', 0, 50),
           ev('fusion.2', 'f32[4,8]', 50, 50)]
    out = op_blocks.block_split(modules, ops, TABLE)
    assert out['matched'] == pytest.approx(0.5)
    assert out['unmatched'] == [['fusion f32[4,8]', pytest.approx(50e-6)]]
    assert op_blocks.block_split([], ops, TABLE) is None
