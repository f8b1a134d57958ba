"""Expanded tokenizer golden set (VERDICT r2 #9): >= 100 sentences across
tokenize_ja / tokenize_cn with full expected segmentations.

Expectations follow Kuromoji/SmartCN conventions for these constructions
(particles split off, verb stem + auxiliary chains split, compound content
words kept whole, counters attached to numerals kept as number + counter).
They were recorded against this segmenter after verifying each matches the
upstream convention for the construction being probed; where Kuromoji
would differ (noted inline) the divergence is a documented cost-model
simplification, not silent.
"""

from hivemall_tpu.frame.ja_segmenter import segment as ja
from hivemall_tpu.frame.cn_segmenter import segment as cn

JA_GOLD = [
    ("私の名前は中野です", ["私", "の", "名前", "は", "中野", "です"]),
    ("すもももももももものうち",
     ["すもも", "も", "もも", "も", "もも", "の", "うち"]),
    ("今日は天気がいい", ["今日", "は", "天気", "が", "いい"]),
    ("明日は雨です", ["明日", "は", "雨", "です"]),
    ("猫が好きです", ["猫", "が", "好き", "です"]),
    ("犬も好きです", ["犬", "も", "好き", "です"]),
    ("学校に行きます", ["学校", "に", "行き", "ます"]),
    ("会社で働きます", ["会社", "で", "働き", "ます"]),
    ("本を読みます", ["本", "を", "読み", "ます"]),
    ("水を飲みます", ["水", "を", "飲み", "ます"]),
    ("ご飯を食べます", ["ご飯", "を", "食べ", "ます"]),
    ("映画を見ます", ["映画", "を", "見", "ます"]),
    ("音楽を聞きます", ["音楽", "を", "聞き", "ます"]),
    ("手紙を書きます", ["手紙", "を", "書き", "ます"]),
    ("電車で帰ります", ["電車", "で", "帰り", "ます"]),
    ("友達と話します", ["友達", "と", "話し", "ます"]),
    ("先生が来ます", ["先生", "が", "来", "ます"]),
    ("母は料理を作ります", ["母", "は", "料理", "を", "作り", "ます"]),
    ("父は新聞を読みます", ["父", "は", "新聞", "を", "読み", "ます"]),
    ("弟は学生です", ["弟", "は", "学生", "です"]),
    ("姉は先生です", ["姉", "は", "先生", "です"]),
    ("駅まで歩きます", ["駅", "まで", "歩き", "ます"]),
    ("家から駅まで", ["家", "から", "駅", "まで"]),
    ("東京に住んでいます", ["東京", "に", "住ん", "で", "い", "ます"]),
    ("日本語を勉強します", ["日本語", "を", "勉強", "し", "ます"]),
    ("電話をかけます", ["電話", "を", "かけ", "ます"]),
    ("写真を見ました", ["写真", "を", "見", "まし", "た"]),
    ("昨日映画を見ました",
     ["昨日", "映画", "を", "見", "まし", "た"]),
    # round 4: 朝ご飯 entered the paradigm lexicon as a compound — kept
    # whole per the header's compound-content-word convention
    ("朝ご飯を食べました",
     ["朝ご飯", "を", "食べ", "まし", "た"]),
    ("お茶を飲みました", ["お茶", "を", "飲み", "まし", "た"]),
    ("部屋で休みます", ["部屋", "で", "休み", "ます"]),
    ("公園を散歩します", ["公園", "を", "散歩", "し", "ます"]),
    ("海で泳ぎます", ["海", "で", "泳ぎ", "ます"]),
    ("山に登ります", ["山", "に", "登り", "ます"]),
    ("空が青い", ["空", "が", "青い"]),
    ("花が美しい", ["花", "が", "美しい"]),
    ("この本は難しい", ["この", "本", "は", "難しい"]),
    ("その店は安い", ["その", "店", "は", "安い"]),
    ("あの人は有名です", ["あの", "人", "は", "有名", "です"]),
    ("どの道が近いですか", ["どの", "道", "が", "近い", "です", "か"]),
    ("今日は忙しいです", ["今日", "は", "忙しい", "です"]),
    ("この問題は簡単です", ["この", "問題", "は", "簡単", "です"]),
    ("仕事が大変です", ["仕事", "が", "大変", "です"]),
    ("質問があります", ["質問", "が", "あり", "ます"]),
    ("時間がありません", ["時間", "が", "あり", "ませ", "ん"]),
    ("お金がない", ["お金", "が", "ない"]),
    ("約束を忘れました", ["約束", "を", "忘れ", "まし", "た"]),
    ("宿題をしました", ["宿題", "を", "し", "まし", "た"]),
    ("試験が終わりました", ["試験", "が", "終わり", "まし", "た"]),
    ("授業が始まります", ["授業", "が", "始まり", "ます"]),
    ("窓を開けます", ["窓", "を", "開け", "ます"]),
    ("扉を閉めます", ["扉", "を", "閉め", "ます"]),
    ("荷物を送ります", ["荷物", "を", "送り", "ます"]),
    ("切符を買いました", ["切符", "を", "買い", "まし", "た"]),
    ("友達を待ちました", ["友達", "を", "待ち", "まし", "た"]),
    ("先生に習います", ["先生", "に", "習い", "ます"]),
    ("言葉を覚えます", ["言葉", "を", "覚え", "ます"]),
    ("毎日勉強します", ["毎日", "勉強", "し", "ます"]),
    ("毎朝走ります", ["毎朝", "走り", "ます"]),
    ("時々映画を見ます", ["時々", "映画", "を", "見", "ます"]),
    # round-5 lexicon expansion (N2 vocabulary bands)
    ("情報を分析します", ["情報", "を", "分析", "し", "ます"]),
    ("新しい方法を提案します", ["新しい", "方法", "を", "提案", "し", "ます"]),
    ("面白い漫画を読みます", ["面白い", "漫画", "を", "読み", "ます"]),
    ("空港まで荷物を運びます", ["空港", "まで", "荷物", "を", "運び", "ます"]),
    ("問題の原因を調べます", ["問題", "の", "原因", "を", "調べ", "ます"]),
    ("会議で意見を述べます", ["会議", "で", "意見", "を", "述べ", "ます"]),
    ("目標を高く掲げます", ["目標", "を", "高く", "掲げ", "ます"]),
    ("経験を活かします", ["経験", "を", "活かし", "ます"]),
]

CN_GOLD = [
    ("我爱北京", ["我", "爱", "北京"]),
    ("今天天气很好", (["今天", "天气", "很", "好"],
     ["今天天气", "很", "好"])),
    ("我是学生", ["我", "是", "学生"]),
    ("他是老师", ["他", "是", "老师"]),
    ("我们在学校学习", ["我们", "在", "学校", "学习"]),
    ("中国的历史很长", ["中国", "的", "历史", "很", "长"]),
    ("我喜欢音乐", ["我", "喜欢", "音乐"]),
    ("她喜欢看电影", ["她", "喜欢", "看", "电影"]),
    ("明天我们去公园", ["明天", "我们", "去", "公园"]),
    ("昨天下雨了", ["昨天", "下雨", "了"]),
    ("北京是中国的首都", ["北京", "是", "中国", "的", "首都"]),
    ("我在公司工作", ["我", "在", "公司", "工作"]),
    ("他去医院看医生", ["他", "去", "医院", "看", "医生"]),
    ("学生在教室上课", ["学生", "在", "教室", "上课"]),
    ("老师回答问题", ["老师", "回答", "问题"]),
    ("我们一起吃饭", ["我们", "一起", "吃饭"]),
    ("他每天跑步", ["他", "每天", "跑步"]),
    ("妈妈在做饭", ["妈妈", "在", "做饭"]),
    ("爸爸看报纸", ["爸爸", "看", "报纸"]),
    ("哥哥在银行工作", ["哥哥", "在", "银行", "工作"]),
    ("妹妹是护士", ["妹妹", "是", "护士"]),
    ("朋友来我家", (["朋友", "来", "我", "家"], ["朋友", "来", "我家"])),
    ("我坐地铁上班", (["我", "坐", "地铁", "上班"],
     ["我", "坐地铁", "上班"])),
    ("他开汽车回家", ["他", "开", "汽车", "回家"]),
    ("我们坐飞机去上海", (["我们", "坐", "飞机", "去", "上海"],
     ["我们", "坐飞机", "去", "上海"])),
    ("火车站很远", ["火车站", "很", "远"]),
    ("机场在城市外面", ["机场", "在", "城市", "外面"]),
    ("图书馆里有很多书", ["图书馆", "里", "有", "很多", "书"]),
    ("这个问题很复杂", ["这个", "问题", "很", "复杂"]),
    ("那个办法很简单", ["那个", "办法", "很", "简单"]),
    ("中文很有趣", ["中文", "很", "有趣"]),
    ("英语比较容易", ["英语", "比较", "容易"]),
    ("经济发展很快", (["经济", "发展", "很", "快"],
     ["经济", "发展", "很快"])),
    ("社会在变化", ["社会", "在", "变化"]),
    ("科学技术很重要", (["科学", "技术", "很", "重要"],
     ["科学技术", "很", "重要"])),
    ("教育是基本问题", ["教育", "是", "基本", "问题"]),
    ("他认为这样不对", (["他", "认为", "这样", "不", "对"],
     ["他", "认为", "这样", "不对"])),
    ("我觉得很高兴", ["我", "觉得", "很", "高兴"]),
    ("大家都知道", ["大家", "都", "知道"]),
    ("我希望明天晴天", ["我", "希望", "明天", "晴天"]),
    ("他需要帮助", ["他", "需要", "帮助"]),
    ("我们决定参加比赛", ["我们", "决定", "参加", "比赛"]),
    ("孩子在公园玩儿", ["孩子", "在", "公园", "玩儿"]),
    ("春天花开了", ["春天", "花", "开", "了"]),
    ("冬天下雪", ["冬天", "下雪"]),
    ("苹果很新鲜", ["苹果", "很", "新鲜"]),
    ("咖啡有点苦", ["咖啡", "有点", "苦"]),
    ("牛奶很便宜", ["牛奶", "很", "便宜"]),
    ("手机在桌子上面", ["手机", "在", "桌子", "上面"]),
    ("电脑是新的", ["电脑", "是", "新", "的"]),
]


def _check(pairs, fn):
    # expect is one exact list, or a (compact, full-dict) tuple — the full
    # system dictionary (round 5) merges some compounds the compact
    # lexicon splits (今天天气, 坐地铁, ...). Pin to the alternative the
    # ACTIVE dictionary should produce, so a regression on either path
    # cannot hide behind the other.
    full = False
    if any(isinstance(e, tuple) for _, e in pairs):   # CN set only — don't
        # make the JA goldens pay the ~2s CN dictionary load
        from hivemall_tpu.frame.cn_segmenter import (segment,
                                                     system_dictionary_info)
        segment("的")  # trigger the lazy dictionary load before reading state
        full = system_dictionary_info()["state"] == "loaded"
    bad = []
    for text, expect in pairs:
        got = fn(text)
        if isinstance(expect, tuple):
            expect = expect[1] if full else expect[0]
        if got != expect:
            bad.append((text, got, expect))
    assert not bad, "\n".join(
        f"{t!r}: got {g} want {e}" for t, g, e in bad[:25])


def test_ja_golden_set():
    assert len(JA_GOLD) >= 60
    _check(JA_GOLD, ja)


def test_cn_golden_set():
    assert len(CN_GOLD) >= 50
    _check(CN_GOLD, cn)


def test_total_golden_count():
    assert len(JA_GOLD) + len(CN_GOLD) >= 100


def _template_golden():
    """Template-generated golden sentences (round 4: VERDICT asks the set
    to pass 500). Boundaries are known BY CONSTRUCTION: sentences are
    assembled from lexicon words in canonical clause shapes, so the
    expected segmentation is the assembly itself; the segmenter must
    recover it from the unspaced surface. The 110+ hand sentences above
    stay the semantic anchor; this block measures boundary recovery at
    scale across paradigm-generated verb forms."""
    from hivemall_tpu.frame.ja_lexicon import (_GODAN, _ICHIDAN,
                                               expand_godan,
                                               expand_ichidan)

    nouns = ("先生 学生 友達 家族 会社 学校 電車 料理 音楽 映画 写真 "
             "新聞 手紙 部屋 公園 病院 銀行 荷物 財布 時計 眼鏡 切符 "
             "朝食 夕食 紅茶 野菜 果物 宿題 試験 授業 仕事 問題 答え "
             "方法 理由 結果 計画 約束 旅行 練習 会議 報告 説明 質問 "
             "連絡 準備 予約 相談 経験 景色 自然 歴史 文化 経済 政治 "
             "技術 科学 音 声 顔 手 足 目 耳 口").split()
    subs = "私 彼 彼女 先生 学生 友達 父 母 兄 姉 弟 妹".split()
    adjs = ("高い 安い 新しい 古い 大きい 小さい 難しい 易しい 広い "
            "狭い 重い 軽い 近い 遠い 明るい 暗い 珍しい 正しい 詳しい "
            "美しい").split()

    godan = _GODAN.split()
    ichidan = _ICHIDAN.split()
    out = []
    # V-renyou + ます over the whole godan paradigm set
    for i, v in enumerate(godan):
        ren = expand_godan(v)[1]
        n = nouns[i % len(nouns)]
        out.append((f"{n}を{ren}ます", [n, "を", ren, "ます"]))
    # ichidan stems + まし/た with subject+は
    for i, v in enumerate(ichidan):
        stem = expand_ichidan(v)[1]
        s = subs[i % len(subs)]
        n = nouns[(i * 7) % len(nouns)]
        out.append((f"{s}は{n}を{stem}ました",
                    [s, "は", n, "を", stem, "まし", "た"]))
    # N1のN2がADJです
    for i, a in enumerate(adjs):
        n1 = subs[i % len(subs)]
        n2 = nouns[(i * 3) % len(nouns)]
        out.append((f"{n1}の{n2}が{a}です",
                    [n1, "の", n2, "が", a, "です"]))
    # N1でN2をV-onbin + た (godan perfective)
    for i, v in enumerate(godan[::2]):
        onbin = expand_godan(v)[2]
        tail = "だ" if v[-1] in "ぐぬぶむ" else "た"   # voiced onbin: 読ん+だ
        n1 = nouns[(i * 5) % len(nouns)]
        n2 = nouns[(i * 11 + 3) % len(nouns)]
        out.append((f"{n1}で{n2}を{onbin}{tail}",
                    [n1, "で", n2, "を", onbin, tail]))
    return out


def test_ja_golden_template_accuracy():
    gold = _template_golden()
    assert len(gold) + len(JA_GOLD) >= 500, (len(gold), len(JA_GOLD))
    bad = []
    for text, expect in gold:
        got = ja(text)
        if got != expect:
            bad.append((text, got, expect))
    acc = 1.0 - len(bad) / len(gold)
    print(f"\ntemplate golden: {len(gold)} sentences, "
          f"accuracy {acc:.3f} ({len(bad)} mismatches); "
          f"total golden set = {len(gold) + len(JA_GOLD)}")
    # boundary-recovery accuracy: constructed sentences can have genuine
    # alternate readings (e.g. a noun absorbing a neighbouring particle
    # into a longer lexicon word), so demand high-but-not-perfect recovery
    assert acc >= 0.9, "\n".join(
        f"{t!r}: got {g} want {e}" for t, g, e in bad[:20])


def test_cn_lexicon_loader_roundtrip(tmp_path):
    """tokenize_cn external-lexicon drop-in (round 4): word+frequency TSV
    and bare-word lines load, frequency maps to lower cost, segmentation
    picks up the new words; vendored behavior restored after."""
    import importlib
    from hivemall_tpu.frame import cn_segmenter as cs

    before = cs.segment("我们在北京学习中文")
    tsv = tmp_path / "lex.tsv"
    tsv.write_text("# comment\n人工智能\t500000\n机器学习\t300000\n"
                   "深度学习\n", encoding="utf-8")
    try:
        n = cs.load_lexicon_tsv(str(tsv))
        assert n == 3
        assert cs.CN_LEXICON["人工智能"] < cs.CN_LEXICON["深度学习"]
        got = cs.segment("我们学习人工智能和机器学习")
        assert "人工智能" in got and "机器学习" in got, got
        assert cs.segment("我们在北京学习中文") == before
    finally:
        importlib.reload(cs)


def test_cn_system_dictionary_loaded():
    """Round 5: tokenize_cn auto-loads the full-coverage frequency
    dictionary from the installed jieba package (MIT, ~349k Han entries)
    on first use — SmartCN-scale coverage out of the box, closing the
    'full dictionaries arrive only via drop-in loaders' gap for Chinese.
    """
    from hivemall_tpu.frame import cn_segmenter as cs

    cs.segment("触发加载")          # trigger the lazy load
    info = cs.system_dictionary_info()
    if info["state"] == "absent":   # image without jieba: fail-soft path
        assert info["entries"] == 0
        return
    assert info["state"] == "loaded"
    assert info["entries"] > 300_000
    assert len(cs.CN_LEXICON) > 300_000
    # classic ambiguous spans the compact lexicon cannot resolve
    assert cs.segment("南京市长江大桥") == ["南京市", "长江大桥"]
    assert cs.segment("研究生命的起源") == ["研究", "生命", "的", "起源"]
    got = cs.segment("人工智能正在改变世界")
    assert "人工智能" in got and "世界" in got, got


def test_cn_system_dictionary_explicit_path(tmp_path):
    """load_system_dictionary(path) parses 'word freq [pos]' lines,
    skips non-Han entries, and maps frequency to cost on the shared
    87/decade scale."""
    import importlib
    from hivemall_tpu.frame import cn_segmenter as cs

    f = tmp_path / "d.txt"
    f.write_text("甲乙丙丁 1000000 n\nABC 50 nz\n丙丁 10 n\n",
                 encoding="utf-8")
    try:
        n = cs.load_system_dictionary(str(f))
        assert n == 2                       # latin entry skipped
        assert cs.CN_LEXICON["甲乙丙丁"] < cs.CN_LEXICON["丙丁"]
    finally:
        importlib.reload(cs)


def test_cn_compact_pin_env():
    """HIVEMALL_TPU_CN_DICT=compact pins the vendored lexicon (fresh
    interpreter: the dictionary state is per-process module state)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["HIVEMALL_TPU_CN_DICT"] = "compact"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", (
        "import sys; sys.path.insert(0, %r)\n"
        "from hivemall_tpu.frame import cn_segmenter as cs\n"
        "assert cs.segment('我们在北京') == ['我们', '在', '北京']\n"
        "info = cs.system_dictionary_info()\n"
        "assert info['state'] == 'off', info\n"
        "assert len(cs.CN_LEXICON) < 2000, len(cs.CN_LEXICON)\n"
    ) % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_cn_user_entries_survive_system_load():
    """User-installed costs take precedence over the lazily-loaded system
    dictionary regardless of load order (install BEFORE the first
    segment() call, then trigger the load — the user's cost must win)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("HIVEMALL_TPU_CN_DICT", None)
    r = subprocess.run([sys.executable, "-c", (
        "import sys; sys.path.insert(0, %r)\n"
        "from hivemall_tpu.frame import cn_segmenter as cs\n"
        "cs.install_entries({'人工智能': 999})\n"
        "cs.segment('触发')\n"                       # lazy system load
        "info = cs.system_dictionary_info()\n"
        "if info['state'] == 'loaded':\n"
        "    assert info['entries'] > 300000, info\n"
        "assert cs.CN_LEXICON['人工智能'] == 999, "
        "cs.CN_LEXICON['人工智能']\n"
    ) % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
